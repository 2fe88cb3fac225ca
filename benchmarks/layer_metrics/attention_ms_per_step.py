"""Device time per training iteration under the scopes of layers of type
Attention, forward and backward: the projections, the rotary positions,
the flash kernels and the re-layouts around them (span_reduce.py). None
for a program that writes no such scope. Layer: Net_layers. Moves
train_samples_per_s in the language-model cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "Attention")
