"""Device runtime up until the `Solver` is built: the program's imports,
prototxt parse, `Net` build, weights from the seed. Layer: CLI_launch.
Moves setup_s."""


def compute(run: dict, trace: dict | None):
    return run["setup_build_s"]
