"""Device time per training iteration under the scopes of layers of type
MoE in the state-space cell, forward and backward: sigmoid scores and the
choice of 6 of 128, sort and gather, the two grouped products over the held
ungated relu^2 experts, the weighted sum back to rows, and the shared
expert every token passes through (span_reduce.py). None for a program that
writes no such scope. Layer: Net_layers. Moves train_samples_per_s in the
state-space cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "MoE")
