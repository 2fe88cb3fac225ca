"""Device time per training iteration under the scopes of layers of type
LRN, forward and backward: the lax fusions in f32, the Pallas kernels in
bf16, and the copies and re-layouts that carry the layer's scope; averaged
over the chips used (span_reduce.py). None for a program that writes no
layer scopes. Layer: Net_layers. Moves train_samples_per_s in the
alexnet cells; ResNet-50 has no LRN."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "LRN")
