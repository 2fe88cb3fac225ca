"""Peak device memory in use on the fullest chip, from the runtime's
`memory_stats()["peak_bytes_in_use"]` after the window. Layer: Device.
Moves train_samples_per_s through the batch that fits."""


def compute(run: dict, trace: dict | None):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
