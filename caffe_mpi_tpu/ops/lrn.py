"""Pallas LRN kernels (TPU): across-channels forward + backward.

Takes the place of the lax path (`ops/lrn_lax.py`; reference
src/caffe/layers/lrn_layer.cpp + lrn_layer.cu: LRNFillScale /
LRNComputeOutput / LRNComputeDiff) where the layer computes in bf16
(ISSUE 9). LRN is pure bandwidth: ~zero MACs over N*C*H*W elements, and
a bf16 operand halves the bytes, so what XLA spends around them weighs
twice (`benchmarks/layer_metrics/lrn_ms_per_step.py` and
`pallas_ms_per_step.py` read what the layer costs in the AlexNet cells;
PERF.md section 5).

Each direction is one kernel that reads its operands once and writes
its result once: forward reads x and writes y; backward reads x and dy,
recomputes the scale in VMEM (cheaper than an HBM round-trip for
residuals), and writes dx:

    y_i  = x_i * s_i^-beta,  s_i = k + (alpha/n) * sum_{W(i)} x_j^2
    dx_m = dy_m * s_m^-beta
           - (2*alpha*beta/n) * x_m * sum_{W(m)} dy_i x_i s_i^{-beta-1}

(the lrn_layer.cu backward identity, computed windowed instead of via
the cross-map convolution trick). Differentiation is wired through
jax.custom_vjp, so `jax.grad` through the training step hits the
backward kernel.

The operand is walked as a 3-D (A, C, L) array with the whole channel
extent C second-minor (the sublanes), so the channel window is `size`
shifted adds in registers. Which axes A and L are follows the operand's
shape alone (`_view`):

- batch a multiple of 128: (H*W, C, N), the batch on the lanes. That is
  the order XLA:TPU already holds a convolution's activations in at
  such a batch ([H][W][C][N]; read from the compiled AlexNet bf16 step,
  b128 to b1024), so the transpose and reshape are bitcasts and no
  copy or pad surrounds the call. H*W is a leading, untiled dimension:
  nothing is padded, a ragged last block is masked by Pallas;
- any other batch (serving buckets, GoogLeNet at 32, a dp shard of 64):
  (N, C, H*W), the spatial extent on the lanes, whole in one block (a
  block dimension equal to the array's needs no lane multiple), several
  samples per block. XLA holds such batches channel-minor, so a
  re-layout each way stays around the call there (PERF.md section 7).

A grid step moves about `_BLOCK_BYTES` per operand (`_blocks`): at 24-64
KB a step's fixed cost (~0.35 us of DMA issue and pipeline bookkeeping)
was the kernel's whole time. Inside a block the body runs over one
(C, 128) tile at a time (`_for_each_tile`). Measured on a v5e at
AlexNet's two shapes, bf16, batch 1024, inside the train step (PR 24,
PERF.md section 6): the forward runs at 630-650 GB/s, the speed of a
plain XLA elementwise pass over the same bytes (625-633); the backward
at 455-515 GB/s, bound by its ~45 f32 VPU operations an element, not by
HBM.

Math is f32 in-kernel regardless of the I/O dtype (bf16 under
`precision: bf16`); outputs cast back at the tile edge. Float32 operands
stay on `ops/lrn_lax.py`: the same two lines in jnp, the window a product
with the 0/1 band, no Pallas import (PERF.md section 6, PR 39). On the
`cpu` platform the same kernels run in Pallas interpreter mode (the CPU
test suite); every other platform compiles them through Mosaic or
fails — ops/pallas_call.py owns that choice."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_call import pallas_call

LANE = 128                # VPU lane count: the minor dimension's tile
_BLOCK_BYTES = 1 << 20    # one operand's block per grid step (see _blocks)
_VMEM_DEFAULT = 16 << 20  # Mosaic's scoped-VMEM limit when none is given


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


def _for_each_tile(tile_fn, in_refs, out_ref):
    """Apply `tile_fn` (f32 (C, T) tiles in, one out) over a whole
    (A, C, L) block, one (C, LANE) tile at a time: a loop over the
    leading axis and one along the lanes, then the short last tile if
    the lane extent is not a LANE multiple. The block is sized for the
    DMA engine; a tile is sized so the body's f32 temporaries stay a few
    dozen vregs instead of spilling a block-sized array each (on the
    v5e, PR 24: the body over the whole block at once is 1.5-1.9x
    slower, 512-lane tiles 1.2-1.4x in the backward). Loops, not a
    Python-unrolled walk: jax traces the body again at every process
    start, whatever the compile cache holds, and an unrolled 24-tile
    body took 1.1 s a kernel there for 4-6 % of kernel time (PR 24)."""
    rows, _, lanes = out_ref.shape
    full, tail = divmod(lanes, LANE)

    def tile(a, cols):
        tiles = [r[a, :, cols].astype(jnp.float32) for r in in_refs]
        out_ref[a, :, cols] = tile_fn(*tiles).astype(out_ref.dtype)

    @pl.loop(0, rows)
    def _(a):
        if full:
            @pl.loop(0, full)
            def _(j):
                tile(a, pl.ds(pl.multiple_of(j * LANE, LANE), LANE))
        if tail:
            tile(a, pl.ds(full * LANE, tail))


def _fwd_kernel(x_ref, y_ref, *, size, alpha, beta, k):
    def tile(x):
        scale = k + _window_sum(x * x, size) * (alpha / size)
        # scale^-beta via exp/log (scale >= k > 0 for every real recipe;
        # the VPU has no direct pow)
        return x * jnp.exp(-beta * jnp.log(scale))
    _for_each_tile(tile, (x_ref,), y_ref)


def _bwd_kernel(x_ref, dy_ref, dx_ref, *, size, alpha, beta, k):
    def tile(x, dy):
        scale = k + _window_sum(x * x, size) * (alpha / size)
        inv_beta = jnp.exp(-beta * jnp.log(scale))  # scale^-beta
        ratio = dy * x * inv_beta / scale           # dy * x * scale^(-b-1)
        return dy * inv_beta \
            - (2.0 * alpha * beta / size) * x * _window_sum(ratio, size)
    _for_each_tile(tile, (x_ref, dy_ref), dx_ref)


def _blocks(a: int, c: int, l: int, itemsize: int) -> tuple[int, int, int]:
    """(rows, lanes, bytes) of the (rows, C, lanes) block over an
    (A, C, L) operand: about `_BLOCK_BYTES` as VMEM tiles it (channels
    rounded up to a tile's 32 bytes of sublanes, lanes to LANE). The
    whole lane extent when that fits (a block dimension equal to the
    array's needs no lane multiple; with L the batch the block is then
    one contiguous run of HBM), otherwise a lane multiple, the ragged
    last block masked by Pallas. Then as many leading rows as fill the
    block."""
    sublanes = 32 // itemsize
    column = -(-c // sublanes) * sublanes * itemsize
    fit = max(LANE, _BLOCK_BYTES // column)     # lanes a block may hold
    lanes = l if -(-l // LANE) * LANE <= fit else fit // LANE * LANE
    tiled_lanes = -(-lanes // LANE) * LANE
    rows = max(1, min(a, fit // tiled_lanes))
    return rows, lanes, rows * column * tiled_lanes


def _run(kernel, name, args, *, size, alpha, beta, k, interpret):
    """Common pallas_call driver: args are (A, C, L) views (_view),
    output mirrors args[0]. `name` is the kernel's name in the HLO and
    in a profiler trace (`lrn_fwd.N`, not `branch_0_fun.N`)."""
    a, c, l = args[0].shape
    rows, lanes, block_bytes = _blocks(a, c, l, args[0].dtype.itemsize)
    spec = pl.BlockSpec((rows, c, lanes), lambda i, j: (i, 0, j))
    # scoped VMEM: every operand's block double-buffered by the pipeline,
    # the f32 temporaries of one (C, LANE) tile (the backward keeps about
    # ten alive), and room for the compiler's own scratch
    vmem = (2 * (len(args) + 1) * block_bytes
            + 10 * -(-c // 8) * 8 * LANE * 4 + (2 << 20))
    return pallas_call(
        functools.partial(kernel, size=size, alpha=alpha, beta=beta, k=k),
        grid=(pl.cdiv(a, rows), pl.cdiv(l, lanes)),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(args[0].shape, args[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(_VMEM_DEFAULT, vmem)),
        interpret=interpret,
        name=name,
    )(*args)


def _batch_on_lanes(shape) -> bool:
    """Which of the two views an (N, C, H, W) operand takes: the batch on
    the lanes once it fills them. XLA:TPU holds a convolution's
    activations batch-minor at such a batch (physically [H][W][C][N], N
    on the lanes and C on the sublanes), so that view is a bitcast of
    what conv, relu and pool already read and write."""
    return shape[0] % LANE == 0


def _view(x):
    """(N, C, H, W) -> the (A, C, L) array the kernels walk, window on
    axis 1: (H*W, C, N) with the batch on the lanes, else (N, C, H*W).
    Neither pads: H*W is either a leading dimension or a whole block
    dimension."""
    n, c, h, w = x.shape
    if _batch_on_lanes(x.shape):
        return x.transpose(2, 3, 1, 0).reshape(h * w, c, n)
    return x.reshape(n, c, h * w)


def _unview(y3, shape):
    n, c, h, w = shape
    if _batch_on_lanes(shape):
        return y3.reshape(h, w, c, n).transpose(3, 2, 0, 1)
    return y3.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn(x, size, alpha, beta, k, interpret):
    y3 = _run(_fwd_kernel, "lrn_fwd", (_view(x),), size=size, alpha=alpha,
              beta=beta, k=k, interpret=interpret)
    return _unview(y3, x.shape)


def _lrn_fwd(x, size, alpha, beta, k, interpret):
    return _lrn(x, size, alpha, beta, k, interpret), x


def _lrn_bwd(size, alpha, beta, k, interpret, x, dy):
    # residual is x alone: the backward kernel recomputes the scale in
    # VMEM — a handful of VPU ops per element against a full extra HBM
    # read+write for a stored-scale residual (LRN is bandwidth-bound,
    # so recompute wins)
    dx3 = _run(_bwd_kernel, "lrn_bwd", (_view(x), _view(dy)), size=size,
               alpha=alpha, beta=beta, k=k, interpret=interpret)
    return (_unview(dx3, x.shape),)


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
