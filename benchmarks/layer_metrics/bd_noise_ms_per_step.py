"""Device time per training iteration under the scopes of the noise layer
(type BlockDiffusionNoise: two uniform draws, a repeat, compares and
selects over L positions) and of the Slice that hands the noisy half to
the head, forward and backward (span_reduce.py). A few tenths of a
millisecond: it is here so that a draw that turns into a sort or a scatter,
or a slice that turns into a copy of both halves, is seen. None for a
program that writes no such scope. Layer: Net_layers. Moves
train_samples_per_s in the block-diffusion cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace,
        lambda row: row["type"] in ("BlockDiffusionNoise", "Slice"))
