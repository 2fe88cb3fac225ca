"""Share of the traced slice in which no operation ran on the device,
averaged over the chips used: 1 - busy union / window (trace_reduce.py).
Layer: Device. Moves train_samples_per_s in every cell."""


def compute(run: dict, trace: dict | None):
    return None if trace is None else 100.0 * trace["idle_share"]
