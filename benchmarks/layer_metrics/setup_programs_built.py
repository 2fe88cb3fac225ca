"""Programs jax built in set-up, compiled or loaded from the cache (the
earlier line `startup_ledger` splits them into hits and misses). Layer:
Compile_cache. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_programs_built")
