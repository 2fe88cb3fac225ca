"""Seconds jax spent tracing Python into jaxprs over every program built in
set-up (`/jax/core/compile/jaxpr_trace_duration`, by the program's own
listener; a trace inside a trace counted once). Layer: Compile_cache.
Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_trace_s")
