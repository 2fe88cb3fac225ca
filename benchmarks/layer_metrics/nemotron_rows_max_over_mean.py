"""Load imbalance of the state-space cell's expert layers: the rows the
busiest held expert received over the mean over the held experts, the worst
layer's. The program's own count (the expert layers' second top) on the
timed batch at iteration 0, read during set-up. 1 is perfect balance. None
for a program whose expert layers offer no such count. Layer: Net_layers.
Moves train_samples_per_s in the state-space cell."""


def compute(run: dict, trace: dict | None):
    layers = [rows for rows in run.get("nemotron_rows", []) if sum(rows)]
    if not layers:
        return None
    return max(max(rows) * len(rows) / sum(rows) for rows in layers)
