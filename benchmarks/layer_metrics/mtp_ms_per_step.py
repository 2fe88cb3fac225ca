"""Device time per training iteration under the scopes of the
multi-token-prediction module's layers (every layer named `mtp/...`: the
shared embedding's second lookup, the two norms, the concatenation and its
4096 -> 2048 product, one more block of latent attention and experts, the
shared head's second use and its loss), forward and backward
(span_reduce.py). None for a program that writes no such scope. Layer:
Net_layers. Moves train_samples_per_s in the latent-attention cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["name"].startswith("mtp/"))
