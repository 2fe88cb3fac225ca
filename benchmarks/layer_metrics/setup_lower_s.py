"""Seconds jax spent lowering jaxprs to MLIR modules over every program
built in set-up (`jaxpr_to_mlir_module_duration`; Mosaic's own lowering of
a Pallas kernel is inside it). Layer: Compile_cache. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_lower_s")
