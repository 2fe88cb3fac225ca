"""Train-step programs the host launched per 100 iterations of the window:
the delta of `Solver.dispatch_count`. 100 at `step_chunk: 1`. Layer:
Solver_loop. Moves train_samples_per_s through device_idle_share."""


def compute(run: dict, trace: dict | None):
    return 100.0 * run["dispatches"] / run["iters"]
