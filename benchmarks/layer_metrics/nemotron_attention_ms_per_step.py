"""Device time per training iteration under the scopes of layers of type
Attention in the state-space cell, forward and backward: the grouped
projections (32 query heads over 2 key/value heads of 128, no positions),
the flash kernels, the output product, and under remat everything before
the kernel once more (span_reduce.py). None for a program that writes no
such scope. Layer: Net_layers. Moves train_samples_per_s in the state-space
cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "Attention")
