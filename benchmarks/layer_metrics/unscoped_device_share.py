"""Share of the device's busy time in operations under no scope of the
program's (no layer scope, not `solver.update`, not `solver.reduce`):
compiler copies, the batch re-layout, eager helpers. 100 for a program
that writes no scopes. Averaged over the chips used (span_reduce.py).
Layer: Device. Moves train_samples_per_s in every cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    spans = span_reduce.for_run(run, trace)
    if spans is None or not spans["n_devices"]:
        return None
    return 100.0 * spans["phase_s"]["unscoped"] / spans["busy_s"]
