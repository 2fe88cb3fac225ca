"""Share of its roofline the hyper-connections reach: the least time the
chip could take for equation 2 of reference/xing_ref.py BY ITS DEFINITION,
the bytes `xing_ref.mhc_cost` counts through HBM a sub-layer (the stream
read and written once a use, forward and backward, nothing computed again;
its products are a few per cent of that time at the bf16 peak and are not
counted) over the HBM peak, times the two sub-layers a block, the blocks
and the traced steps, over the device time of the HyperConnectionPre and
HyperConnectionPost layers (mhc_ms_per_step). The count is of the
definition, so a recomputation under remat is charged in the time alone and
the share cannot pass 100. None where those layers wrote no scope or the
run is no hyper-connection cell's. Layer: Net_layers. Moves
train_samples_per_s in the hyper-connection cell."""

import span_reduce
from reference import xing_ref

TYPES = ("HyperConnectionPre", "HyperConnectionPost")


def compute(run: dict, trace: dict | None):
    if "xing_sizes" not in run:
        return None
    ms = span_reduce.layer_ms_per_step(
        run, trace, lambda row: row["type"] in TYPES)
    if not ms:
        return None
    sz, peaks = xing_ref.sizes_from_record(run["xing_sizes"]), run["peaks"]
    nbytes = xing_ref.mhc_cost(sz, run["samples_per_iter"], run["seq_len"])
    least_ms = 1e3 * 2 * sz.layers * nbytes / peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
