"""Device time per training iteration of every operation under the
program's `mhc.sinkhorn` scope, forward, recomputed and backward: the
Sinkhorn-Knopp iterations that make each token's residual mix H_res doubly
stochastic, 20 times rows then columns of a 4 x 4 matrix a token, inside
the HyperConnectionPre layers (scope_reduce.py). None where no operation
carries the scope. Layer: Net_layers. Moves train_samples_per_s in the
hyper-connection cell."""

import scope_reduce

SCOPE = "mhc.sinkhorn"


def compute(run: dict, trace: dict | None):
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds:
        return None
    return 1e3 * seconds / run["traced_iters"]
