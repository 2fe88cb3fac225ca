"""Device time per training iteration under the scopes of layers of type
Attention in the block-diffusion cell, forward and backward: the
projections, the per-head q/k norms, the rotary turn at i mod L, the flash
kernels under the block mask over 2 L rows and the re-layouts around them
(span_reduce.py). None for a program that writes no such scope. Layer:
Net_layers. Moves train_samples_per_s in the block-diffusion cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "Attention")
