"""Share of their roofline the expert layers' grouped matrix products
reach: the least time the chip could take for the rows actually routed to
held experts (per layer the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, reference/lm_ref.grouped_cost: rows x 3 x hidden x
expert width multiply-accumulates forward, twice that backward), over the
device time of every operation under the program's `moe.experts` scope,
whatever implements it (scope_reduce.py). The rows are the program's own
count on the timed batch (the expert layers' second top): the mean of its
readings at iteration 0, during set-up, and after the window. The recipe
holds routing constant under training (router frozen, no gradient through
it), so the two differ only by what the other weights' training moves, and
the traced slice lies between them. None
where no operation carries the scope. Layer: Pallas_kernels. Moves
train_samples_per_s in the language-model cell."""

import scope_reduce
from reference import lm_ref

SCOPE = "moe.experts"


def compute(run: dict, trace: dict | None):
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds or "moe_rows" not in run:
        return None
    sz, peaks = lm_ref.sizes_from_record(run["sizes"]), run["peaks"]
    least = 0.0
    for before, after in zip(run["moe_rows"], run["moe_rows_after"]):
        flops, nbytes = lm_ref.grouped_cost(
            int(sum(before) + sum(after)) // 2, sz)
        least += 3 * max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
