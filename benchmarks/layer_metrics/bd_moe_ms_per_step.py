"""Device time per training iteration under the scopes of layers of type
MoE in the block-diffusion cell, forward and backward: router, sort and
gather, the grouped products over the held experts for the 2 L rows, the
weighted sum back to rows (span_reduce.py). None for a program that writes
no such scope. Layer: Net_layers. Moves train_samples_per_s in the
block-diffusion cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "MoE")
