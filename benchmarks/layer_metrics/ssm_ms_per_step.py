"""Device time per training iteration under the scopes of layers of type
Mamba2, forward and backward: the input product, the causal convolution a
channel, the chunked selective scan (computed again in the backward pass),
the gate and the grouped norm, the output product (span_reduce.py). None for
a program that writes no such scope. Layer: Net_layers. Moves
train_samples_per_s in the state-space cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "Mamba2")
