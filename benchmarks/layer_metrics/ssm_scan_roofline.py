"""Share of its roofline the selective state-space recurrence reaches: the
least time the chip could take for equation 5 BY ITS DEFINITION (per layer
the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
reference/nemotron_ref.scan_cost: a position's state update and read-out,
2 H P N multiply-accumulates, which no chunked form undercuts, and x, B, C,
delta and y through memory once; the backward pass twice that again), times
the Mamba-2 layers and the traced steps, over the device time of every
operation under the program's `ssm.scan` scope (scope_reduce.py). The count
is of the definition, so it reads the same work whatever implements the
scan (a chunked form does more products and a recomputation, and is charged
for them in the time alone) and cannot pass 100. None where no operation
carries the scope or the run is no state-space cell's. Layer: Net_layers.
Moves train_samples_per_s in the state-space cell."""

import scope_reduce
from reference import nemotron_ref

SCOPE = "ssm.scan"


def compute(run: dict, trace: dict | None):
    if "nemotron_sizes" not in run:
        return None
    seconds = scope_reduce.for_run(run, trace, SCOPE)
    if not seconds:
        return None
    sz, peaks = nemotron_ref.sizes_from_record(run["nemotron_sizes"]), \
        run["peaks"]
    flops, nbytes = nemotron_ref.scan_cost(sz, run["samples_per_iter"],
                                           run["seq_len"])
    least = len(sz.of_kind("M")) * max(flops / peaks["bf16_flops_per_s"],
                                       nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_iters"] / seconds
