"""Device time per training iteration under the scopes of the latent
attention layers (type Attention; in this cell every one of them is
latent: five blocks' and the MTP module's), forward and backward: the
low-rank projections and the norms between their factors, the rotary
lanes, the concatenations that build the 192-wide operands, the flash
kernels and the re-layouts around them (span_reduce.py). None for a
program that writes no such scope. Layer: Net_layers. Moves
train_samples_per_s in the latent-attention cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] == "Attention")
