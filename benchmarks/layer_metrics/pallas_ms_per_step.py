"""Device time in Mosaic (Pallas) kernels per training iteration: the
`tpu_custom_call` events of the traced slice, averaged over the chips used.
Layer: Pallas_kernels. Moves train_samples_per_s in alexnet_bf16; reads 0
where the step holds no kernel."""


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters"):
        return None
    seconds = sum(k["seconds"] for k in trace["custom_calls"].values())
    return 1e3 * seconds / run["traced_iters"]
