"""Share of their roofline the block-diffusion cell's grouped matrix
products reach: the least time the chip could take for the rows actually
routed to held experts (per layer the larger of FLOPs over the bf16 peak
and bytes over the HBM peak, reference/sdar_ref.grouped_cost: rows x 3 x
hidden x expert width multiply-accumulates forward, twice that backward),
over the device time of every operation under the program's `moe.experts`
scope (scope_reduce.py). The rows are the program's own count on the timed
batch (the expert layers' second top): the mean of its readings at
iteration 0, during set-up, and after the window, each on one noise draw;
routing is a constant of the step, so the two differ only by the draw and
by what the other weights' training moves. None where no operation carries
the scope. Layer: Pallas_kernels. Moves train_samples_per_s in the
block-diffusion cell."""

import scope_reduce
from reference import sdar_ref

SCOPE = "moe.experts"


def compute(run: dict, trace: dict | None):
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds or "bd_rows" not in run:
        return None
    sz, peaks = sdar_ref.sizes_from_record(run["bd_sizes"]), run["peaks"]
    least = 0.0
    for before, after in zip(run["bd_rows"], run["bd_rows_after"]):
        flops, nbytes = sdar_ref.grouped_cost(
            int(sum(before) + sum(after)) // 2, sz)
        least += 3 * max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
