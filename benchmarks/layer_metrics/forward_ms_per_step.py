"""Device time per training iteration in operations under a prototxt
layer's scope in the forward pass (`caffe.<Type>.<name>`, no `transpose(`
in the `op_name`), over the traced slice, averaged over the chips used
(span_reduce.py). None for a program that writes no layer scopes. Layer:
Net_layers. Moves train_samples_per_s in every cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["phase"] == "forward")
