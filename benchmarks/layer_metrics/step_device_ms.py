"""Device busy time per training iteration in the traced slice: the union
of the device's leaf operations over the slice's iterations, averaged over
the chips used. Layer: Net_layers (the XLA step). Moves
train_samples_per_s in every cell."""


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters"):
        return None
    return 1e3 * trace["busy_s"] / run["traced_iters"]
