"""Device idle time between programs, per traced iteration and chip,
while `caffe/solver/feed wait` was the innermost `caffe/solver/*` span
open on the host (span_reduce.py). None for a program that writes no
spans. Layer: Feeder. Moves train_samples_per_s."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.span_ms_per_iter(run, trace, "feed wait",
                                        table="idle_by_span_s")
