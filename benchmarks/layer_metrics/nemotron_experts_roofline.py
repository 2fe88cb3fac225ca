"""Share of their roofline the state-space cell's grouped matrix products
reach: the least time the chip could take for the rows actually routed to
held experts (per expert layer the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, reference/nemotron_ref.grouped_cost: rows x 2 x
hidden x expert width multiply-accumulates forward, an ungated expert's two
matrices, twice that backward), over the device time of every operation
under the program's `moe.experts` scope (scope_reduce.py). The rows are the
program's own count on the timed batch (the expert layers' second top): the
mean of its readings at iteration 0, during set-up, and after the window;
routing is a constant of the step, so the two differ only by what the other
weights' training moves. None where no operation carries the scope or the
run is no state-space cell's. Layer: Pallas_kernels. Moves
train_samples_per_s in the state-space cell."""

import scope_reduce
from reference import nemotron_ref

SCOPE = "moe.experts"


def compute(run: dict, trace: dict | None):
    if "nemotron_rows" not in run:
        return None
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds:
        return None
    sz, peaks = nemotron_ref.sizes_from_record(run["nemotron_sizes"]), \
        run["peaks"]
    least = 0.0
    for before, after in zip(run["nemotron_rows"],
                             run["nemotron_rows_after"]):
        flops, nbytes = nemotron_ref.grouped_cost(
            int(sum(before) + sum(after)) // 2, sz)
        least += 3 * max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
