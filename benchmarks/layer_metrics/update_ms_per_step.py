"""Device time per training iteration under the `solver.update` scope:
unscale, clip, LR policy, optimizer update, master-weight cast, skip-step
guard; averaged over the chips used (span_reduce.py). None for a program
that writes no such scope. Layer: Solver_loop. Moves train_samples_per_s
in every cell."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["phase"] == "update")
