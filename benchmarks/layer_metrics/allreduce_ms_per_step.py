"""Time per training iteration in gradient all-reduces, from the traced
slice: a synchronous all-reduce event, or all-reduce-start through its
all-reduce-done, averaged over the chips. Layer: Parallel. Moves
train_samples_per_s in the cells on several chips; absent on one."""


def compute(run: dict, trace: dict | None):
    if trace is None or run["chips"] < 2 or not run.get("traced_iters"):
        return None
    return 1e3 * trace["allreduce"]["seconds"] / run["traced_iters"]
