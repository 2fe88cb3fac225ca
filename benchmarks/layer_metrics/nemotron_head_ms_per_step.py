"""Device time per training iteration under the scopes of the embedding,
the head and the token loss in the state-space cell: the Embed (a gather
forward, its transpose backward), the InnerProduct named `logits` and
SoftmaxWithLoss, forward and backward (span_reduce.py): with 9 of 52 layers
the head is 12 % of the multiply-accumulates where a sixteenth of it is 1 %
of the whole model's. None for a program that writes no such scope. Layer:
Net_layers. Moves train_samples_per_s in the state-space cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] in ("SoftmaxWithLoss", "Embed")
        or (row["type"] == "InnerProduct" and row["name"] == "logits"))
