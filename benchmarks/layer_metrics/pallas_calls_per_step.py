"""Mosaic (Pallas) kernel launches per training iteration, counted from the
`tpu_custom_call` events of the traced slice, averaged over the chips used.
Layer: Pallas_kernels. Moves train_samples_per_s in alexnet_bf16; must read
0 in a cell whose configuration states no kernel."""


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters"):
        return None
    calls = sum(k["count"] for k in trace["custom_calls"].values())
    return calls / run["traced_iters"]
