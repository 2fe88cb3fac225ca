"""Share of their roofline the flash-attention kernels reach at latent
attention's unequal widths: the least time the chip could take for the
calls of `flash_fwd`, `flash_dq` and `flash_dkv` in the traced slice (per
call the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
counted by reference/joyai_ref.flash_cost from the shapes: products over
the 128 x 128 tiles the causal mask leaves, QK^T and its two backward uses
over 192 lanes, PV and its two over 128, each operand read once; a lane
the MXU pads is not credited), over the device time of those kernels'
events. One call of each kernel a block and one in the MTP module. None
where the trace holds no kernel of those names. Layer: Pallas_kernels.
Moves train_samples_per_s in the latent-attention cell."""

from reference import joyai_ref

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def compute(run: dict, trace: dict | None):
    if trace is None or not run.get("traced_iters") \
            or "mla_sizes" not in run:
        return None
    calls = {k: trace["custom_calls"][k] for k in KERNELS
             if k in trace["custom_calls"]}
    seconds = sum(c["seconds"] for c in calls.values())
    if not seconds:
        return None
    sz, peaks = joyai_ref.sizes_from_record(run["mla_sizes"]), run["peaks"]
    least = 0.0
    for kernel in calls:
        flops, nbytes = joyai_ref.flash_cost(
            kernel, sz, run["samples_per_iter"], run["seq_len"])
        least += (sz.layers + 1) * max(flops / peaks["bf16_flops_per_s"],
                                       nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
