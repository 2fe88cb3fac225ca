"""Backend seconds over every program built in set-up
(`backend_compile_duration`): reading and loading executables from the
persistent cache on a warm run, XLA's compile on a cold one. Layer:
Compile_cache. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_backend_s")
