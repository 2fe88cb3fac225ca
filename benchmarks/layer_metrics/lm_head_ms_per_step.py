"""Device time per training iteration under the scopes of the output head
(the InnerProduct named `logits`) and of the token loss (SoftmaxWithLoss),
forward and backward (span_reduce.py): with 4 of 52 layers the head is
about 30 % of the FLOPs where it is 3 % in the whole model. None for a
program that writes no such scope. Layer: Net_layers. Moves
train_samples_per_s in the language-model cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "SoftmaxWithLoss"
        or (row["type"] == "InnerProduct" and row["name"] == "logits"))
