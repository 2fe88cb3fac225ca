"""Host self time of the span `caffe/solver/feed wait` per traced
iteration: batch assembly, the `[1, B, ...]` re-layout and host-to-device
placement in `Solver.step` (span_reduce.py). None for a program that
writes no spans. Layer: Feeder. Moves train_samples_per_s where the device
waits for it (feed_wait_idle_ms_per_iter)."""

import span_reduce


def compute(run: dict, trace: dict | None):
    return span_reduce.span_ms_per_iter(run, trace, "feed wait")
