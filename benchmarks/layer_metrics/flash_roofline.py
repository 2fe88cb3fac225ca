"""Share of their roofline the flash-attention kernels reach: the least
time the chip could take for the calls of `flash_fwd`, `flash_dq` and
`flash_dkv` in the traced slice (per call the larger of FLOPs over the bf16
peak and bytes over the HBM peak, FLOPs and bytes counted by
reference/lm_ref.flash_cost from the shapes: matrix products over the 128 x
128 tiles the mask leaves, each operand read once), over the device time
of those kernels' events. None where the trace holds no kernel of those
names. Layer: Pallas_kernels. Moves train_samples_per_s in the
language-model cell."""

from reference import lm_ref

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters") or "sizes" not in run:
        return None
    calls = {k: trace["custom_calls"][k] for k in KERNELS
             if k in trace["custom_calls"]}
    seconds = sum(c["seconds"] for c in calls.values())
    if not seconds:
        return None
    sz, peaks = lm_ref.sizes_from_record(run["sizes"]), run["peaks"]
    least = 0.0
    for kernel in calls:
        for layer in range(sz.layers):
            flops, nbytes = lm_ref.flash_cost(
                kernel, sz, layer, run["samples_per_iter"], run["seq_len"])
            least += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
