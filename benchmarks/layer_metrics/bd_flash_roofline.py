"""Share of their roofline the flash-attention kernels reach under the
block-diffusion mask: the least time the chip could take for the calls of
`flash_fwd`, `flash_dq` and `flash_dkv` in the traced slice (per call the
larger of FLOPs over the bf16 peak and bytes over the HBM peak, counted by
reference/sdar_ref.flash_cost from the shapes: matrix products over the 128
x 128 tiles of the 2 L x 2 L square that hold a visible pair by the mask's
definition, whatever tiles the kernels visit; each operand read once), over
the device time of those kernels' events. One call of each kernel a
layer. None where the trace holds no kernel of those names. Layer:
Pallas_kernels. Moves train_samples_per_s in the block-diffusion cell."""

from reference import sdar_ref

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters") \
            or "bd_sizes" not in run:
        return None
    calls = {k: trace["custom_calls"][k] for k in KERNELS
             if k in trace["custom_calls"]}
    seconds = sum(c["seconds"] for c in calls.values())
    if not seconds:
        return None
    sz, peaks = sdar_ref.sizes_from_record(run["bd_sizes"]), run["peaks"]
    least = 0.0
    for kernel in calls:
        flops, nbytes = sdar_ref.flash_cost(
            kernel, sz, run["samples_per_iter"], run["seq_len"])
        least += sz.layers * max(flops / peaks["bf16_flops_per_s"],
                                 nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
