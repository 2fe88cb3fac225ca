"""Display-boundary host syncs per 100 iterations of the window: the delta
of `Solver.host_sync_count`. Each drains the device's queue. (The
benchmark's own wait at the end of each `solver.step` block falls on the
same iteration and is printed on an earlier line as `block_end_syncs`.)
Layer: Solver_loop. Moves train_samples_per_s through device_idle_share."""


def compute(run: dict, trace: dict | None):
    return 100.0 * run["host_syncs"] / run["iters"]
