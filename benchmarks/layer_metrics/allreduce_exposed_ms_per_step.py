"""The part of allreduce_ms_per_step during which no other operation ran
on that chip: the collective time the backward pass did not hide. Layer:
Parallel. Moves train_samples_per_s in the cells on several chips; absent
on one."""


def compute(run: dict, trace: dict | None):
    if trace is None or run["chips"] < 2 or not run.get("traced_iters"):
        return None
    return 1e3 * trace["allreduce"]["exposed_seconds"] / run["traced_iters"]
