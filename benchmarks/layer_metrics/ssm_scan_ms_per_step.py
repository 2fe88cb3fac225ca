"""Device time per training iteration of every operation under the
program's `ssm.scan` scope, forward, recomputed and backward: softplus, the
decays and the selective state-space recurrence of the Mamba-2 layers,
whatever computes it (today four products a chunk and the carried states,
ops/ssd.py) (scope_reduce.py). None where no operation carries the scope.
Layer: Net_layers. Moves train_samples_per_s in the state-space cell."""

import scope_reduce

SCOPE = "ssm.scan"


def compute(run: dict, trace: dict | None):
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds:
        return None
    return 1e3 * seconds / run["traced_iters"]
