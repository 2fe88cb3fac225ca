"""The first `solver.step` call until its result is ready: trace and lower
the step, compile it or load it from the cache, run iteration 0. Layer:
Compile_cache. Moves setup_s."""


def compute(run: dict, trace: dict | None):
    return run["setup_compile_s"]
