"""Share of their roofline the flash-attention kernels reach in the
state-space cell (32 query heads over 2 key/value heads of 128, causal, 16
query heads a group): the least time the chip could take for the calls of
`flash_fwd`, `flash_dq` and `flash_dkv` in the traced slice (per call the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, counted by
reference/nemotron_ref.flash_cost from the shapes: matrix products over the
128 x 128 tiles the causal mask leaves, each operand read once), over the
device time of those kernels' events. One call of each kernel an attention
layer. None where the trace holds no kernel of those names or the run is no
state-space cell's. Layer: Pallas_kernels. Moves train_samples_per_s in the
state-space cell."""

from reference import nemotron_ref

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters") \
            or "nemotron_sizes" not in run:
        return None
    calls = {k: trace["custom_calls"][k] for k in KERNELS
             if k in trace["custom_calls"]}
    seconds = sum(c["seconds"] for c in calls.values())
    if not seconds:
        return None
    sz, peaks = nemotron_ref.sizes_from_record(run["nemotron_sizes"]), \
        run["peaks"]
    least = 0.0
    for kernel in calls:
        flops, nbytes = nemotron_ref.flash_cost(
            kernel, sz, run["samples_per_iter"], run["seq_len"])
        least += len(sz.of_kind("*")) * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
