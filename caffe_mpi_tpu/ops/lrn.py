"""Pallas LRN kernels (TPU): across-channels forward + backward.

Replaces the lax path of `layers/vision.py LRNLayer` (reference
src/caffe/layers/lrn_layer.cpp + lrn_layer.cu: LRNFillScale /
LRNComputeOutput / LRNComputeDiff) for the bf16 roofline offender case
(ISSUE 9). LRN is pure bandwidth: ~zero MACs over N*C*H*W elements,
and the stock lowering (reduce_window for the channel-window sum, a
power, and reverse-mode AD re-materializing the scale) makes several
full HBM passes over the activation per direction. tools/mfu_analysis.py
ranks it the worst bandwidth-bound layer of the AlexNet bench config
once bf16 lifts the convs toward MXU peak.

These kernels make each direction ONE pass: a (1, C, T) VMEM tile per
grid step holds the whole channel extent, so the 5-wide channel window
sum, the scale, and the power all happen in registers — forward reads x
and writes y; backward reads x and dy, recomputes the scale in VMEM
(cheaper than an HBM round-trip for residuals), and writes dx:

    y_i  = x_i * s_i^-beta,  s_i = k + (alpha/n) * sum_{W(i)} x_j^2
    dx_m = dy_m * s_m^-beta
           - (2*alpha*beta/n) * x_m * sum_{W(m)} dy_i x_i s_i^{-beta-1}

(the lrn_layer.cu backward identity, computed windowed instead of via
the cross-map convolution trick). Differentiation is wired through
jax.custom_vjp, so `jax.grad` through the training step hits the
backward kernel.

Math is f32 in-kernel regardless of the I/O dtype (bf16 under
`precision: bf16`); outputs cast back at the tile edge. The jnp path in
vision.py remains the numerical reference and the f32 default. On the
`cpu` platform the same kernels run in Pallas interpreter mode (the
CPU test suite); every other platform compiles them through Mosaic or
fails — ops/pallas_call.py owns that choice."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_call import pallas_call

LANE = 128  # spatial tile width (VPU lane count)


def _window_sum(t, size):
    """Centered channel-window sum of a (C, T) tile: out[i] =
    sum_{j in [i-half, i+half]} t[j], zero beyond the edges — exactly
    the reference's channel-window truncation (lrn_layer.cpp:94-116).
    `size` is a static python int, so this unrolls into `size` shifted
    adds on the VPU (no gather, no reduce_window)."""
    half = (size - 1) // 2
    c, w = t.shape
    zeros = jnp.zeros((half, w), t.dtype)
    padded = jnp.concatenate([zeros, t, zeros], axis=0)
    out = padded[0:c]
    for off in range(1, size):
        out = out + padded[off:off + c]
    return out


def _fwd_kernel(x_ref, y_ref, *, size, alpha, beta, k):
    x = x_ref[0].astype(jnp.float32)            # (C, T)
    scale = k + _window_sum(x * x, size) * (alpha / size)
    # scale^-beta via exp/log (scale >= k > 0 for every real recipe;
    # the VPU has no direct pow)
    y = x * jnp.exp(-beta * jnp.log(scale))
    y_ref[0] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, dx_ref, *, size, alpha, beta, k):
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    scale = k + _window_sum(x * x, size) * (alpha / size)
    inv_beta = jnp.exp(-beta * jnp.log(scale))  # scale^-beta
    ratio = dy * x * inv_beta / scale           # dy * x * scale^(-b-1)
    dx = dy * inv_beta \
        - (2.0 * alpha * beta / size) * x * _window_sum(ratio, size)
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _tile(sp: int) -> tuple[int, int]:
    """(padded spatial length, tile width): a single short tile is legal
    as-is (block dims equal to array dims satisfy Mosaic's tiling
    rule); longer extents round up to LANE multiples."""
    if sp <= LANE:
        return sp, sp
    return -(-sp // LANE) * LANE, LANE


def _run(kernel, name, args, *, size, alpha, beta, k, interpret):
    """Common pallas_call driver: args are (N, C, SP) arrays (already
    lane-padded), output mirrors args[0]. `name` is the kernel's name in
    the HLO and in a profiler trace (`lrn_fwd.N`, not `branch_0_fun.N`)."""
    n, c, sp = args[0].shape
    sp_pad, t = _tile(sp)
    spec = pl.BlockSpec((1, c, t), lambda i, j: (i, 0, j))
    return pallas_call(
        functools.partial(kernel, size=size, alpha=alpha, beta=beta, k=k),
        grid=(n, sp_pad // t),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(args[0].shape, args[0].dtype),
        interpret=interpret,
        name=name,
    )(*args)


def _prep(x):
    """(N, C, H, W) -> lane-padded (N, C, SP) plus the restore info.
    Padded spatial columns are all-zero; the channel window never mixes
    columns, so they stay exact zeros and slice off losslessly."""
    n, c, h, w = x.shape
    sp = h * w
    x3 = x.reshape(n, c, sp)
    sp_pad, _ = _tile(sp)
    if sp_pad != sp:
        x3 = jnp.pad(x3, ((0, 0), (0, 0), (0, sp_pad - sp)))
    return x3, (n, c, h, w, sp)


def _restore(y3, shape_info):
    n, c, h, w, sp = shape_info
    return y3[:, :, :sp].reshape(n, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn(x, size, alpha, beta, k, interpret):
    x3, info = _prep(x)
    y3 = _run(_fwd_kernel, "lrn_fwd", (x3,), size=size, alpha=alpha, beta=beta,
              k=k, interpret=interpret)
    return _restore(y3, info)


def _lrn_fwd(x, size, alpha, beta, k, interpret):
    return _lrn(x, size, alpha, beta, k, interpret), x


def _lrn_bwd(size, alpha, beta, k, interpret, x, dy):
    # residual is x alone: the backward kernel recomputes the scale in
    # VMEM — a handful of VPU ops per element against a full extra HBM
    # read+write for a stored-scale residual (LRN is bandwidth-bound,
    # so recompute wins)
    x3, info = _prep(x)
    dy3, _ = _prep(dy)
    dx3 = _run(_bwd_kernel, "lrn_bwd", (x3, dy3), size=size, alpha=alpha,
               beta=beta, k=k, interpret=interpret)
    return (_restore(dx3, info),)


_lrn.defvjp(_lrn_fwd, _lrn_bwd)


def lrn_across_channels(x: jnp.ndarray, size: int, alpha: float,
                        beta: float, k: float,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Across-channels LRN over a (N, C, H, W) blob — the AlexNet /
    CaffeNet norm_region=ACROSS_CHANNELS case. Differentiable
    (custom_vjp -> the Pallas backward kernel). `interpret=None` =
    interpreter on the cpu platform, Mosaic on any other
    (ops/pallas_call.py)."""
    if x.ndim != 4:
        raise ValueError(f"lrn_across_channels expects NCHW, got "
                         f"shape {x.shape}")
    if size % 2 != 1:
        raise ValueError("LRN local_size must be odd")
    return _lrn(x, int(size), float(alpha), float(beta), float(k),
                interpret)
