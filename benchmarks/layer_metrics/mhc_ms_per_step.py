"""Device time per training iteration under the scopes of the
hyper-connection layers (types HyperConnectionPre and HyperConnectionPost:
two of each a block, around attention and around the feed-forward),
forward, recomputed and backward: the norm over the n C lanes, the
coefficients' product, sigmoid, the Sinkhorn-Knopp iterations, H_pre x and
H_res x + H_post^T F (span_reduce.py). None for a program that writes no
such scope. Layer: Net_layers. Moves train_samples_per_s in the
hyper-connection cell."""

import span_reduce

TYPES = ("HyperConnectionPre", "HyperConnectionPost")


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] in TYPES)
