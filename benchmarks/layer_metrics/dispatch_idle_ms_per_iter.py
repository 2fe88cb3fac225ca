"""Device idle time between programs, per traced iteration and chip,
while `caffe/solver/train dispatch` was the innermost `caffe/solver/*`
span open on the host (span_reduce.py). None for a program that writes no
spans. Layer: Solver_loop. Moves train_samples_per_s."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.span_ms_per_iter(run, trace, "train dispatch",
                                        table="idle_by_span_s")
