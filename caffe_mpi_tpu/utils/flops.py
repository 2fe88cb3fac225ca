"""Analytic FLOPs / MFU accounting.

Replaces: nothing in the reference — Caffe-MPI reports img/s only
(solver.cpp:619-628). MFU (model FLOPs utilization: achieved FLOP/s over
the chip's peak) is the TPU-native efficiency metric: img/s depends on the
model, MFU says how much of the MXU the program actually keeps busy, which
is what XLA tuning moves.

The count is *model* FLOPs (the textbook cost of the layers, not whatever
the compiler executed): conv and matmul MACs only — elementwise/pool/norm
ops are HBM-bound noise next to the MXU terms. Backward costs 2x forward
(one matmul each for d-input and d-weight per forward matmul).

The per-type MAC formulas live in proto/netshape.py (`macs_per_image`) —
ONE spelling shared with the jax-free netlint/summarize path (ISSUE 15);
this module adapts built Layer objects onto it for the bench tools.
"""

from __future__ import annotations


def layer_macs_per_image(layer) -> int:
    """Multiply-accumulates per image/sample for one built layer (0 for
    non-MXU ops). Delegates to the static engine's MAC model so the
    bench/MFU accounting and the prototxt-level analysis cannot drift."""
    from ..proto.netshape import macs_per_image
    macs = macs_per_image(
        layer.type_name, layer.in_shapes, layer.out_shapes,
        {name: tuple(decl.shape) for name, decl in layer.params.items()},
        layer.lp)
    return int(macs or 0)


def net_macs_per_image(net) -> int:
    return sum(layer_macs_per_image(l) for l in net.layers)


def train_flops_per_image(net) -> int:
    """fwd (2 FLOPs/MAC) + bwd (2x fwd: d-input and d-weight matmuls)."""
    return 6 * net_macs_per_image(net)


# Peak dense-matmul FLOP/s per chip at the MXU's native precision
# (bf16 multiply, f32 accumulate) — the denominator for MFU. Sources:
# jax-ml.github.io/scaling-book hardware table / Google Cloud TPU docs.
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 138e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float | None:
    """Peak FLOP/s for a jax device. The host CPU has no entry and no
    peak: None, and callers print no MFU. Any other device missing from
    the table is an error, not an MFU of None — a utilization against an
    unknown peak is not a number."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    # longest prefix wins: 'TPU v5 lite pod' must match 'TPU v5 lite',
    # not 'TPU v5'
    for k in sorted(PEAK_FLOPS_BY_KIND, key=len, reverse=True):
        if kind.startswith(k):
            return PEAK_FLOPS_BY_KIND[k]
    raise ValueError(
        f"no peak FLOP/s on record for device kind {kind!r} (platform "
        f"{device.platform!r}); add it to PEAK_FLOPS_BY_KIND with its "
        f"source")
