"""Host self time of the span `caffe/solver/train dispatch` per traced
iteration: launching the train program from `Solver.step`
(span_reduce.py). None for a program that writes no spans. Layer:
Solver_loop. Moves train_samples_per_s where the device waits for it
(dispatch_idle_ms_per_iter)."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.span_ms_per_iter(run, trace, "train dispatch")
