"""Device time per training iteration under the program's `moe.shared`
scope in the state-space cell: the shared ungated relu^2 expert's two
products (width 3,712), which every token passes through in each of the
four expert layers, forward and backward (scope_reduce.py). None where no
operation carries the scope. Layer: Net_layers. Moves train_samples_per_s
in the state-space cell."""

import scope_reduce

SCOPE = "moe.shared"


def compute(run: dict, trace: dict | None):
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds:
        return None
    return 1e3 * seconds / run["traced_iters"]
