"""Pallas flash-attention kernels (TPU): forward + backward.

The Pallas path of the framework: where XLA's fusion isn't enough, ops drop
to hand-written TPU kernels (the reference's analogue is its hand-written
CUDA kernels next to cuDNN ops). Attention is the canonical case — naive
attention materializes the (Sq, Sk) score matrix in HBM; these kernels keep
it in VMEM tiles with an online softmax, O(S) memory instead of O(S^2).

Layout: (B, H, S, D) inside the kernels (sequence-minor tiles). The public
entry accepts the framework's (B, S, H, D) and transposes at the edges.
Tiles are 128 to 512 long (`_tile`, from the sequence length alone).
Grouped heads: K and V have B*Hkv rows and query row i reads row i // (H /
Hkv) through the block index map. A causal window joins the tile mask, and
tiles wholly outside it or above the diagonal are not visited, in all three
kernels. bf16 operands go into the MXU as they are, accumulated in float32.
Forward grid: (B*H, Sq/BQ) with an inner fori_loop over K tiles,
accumulating (out, m, l) in registers; it also emits the per-row
logsumexp, which the backward re-uses to recompute normalized
probabilities tile-by-tile (FlashAttention-2 style) instead of storing P:
  dQ kernel: grid (B*H, Sq/BQ), loops K tiles; dS = P * (dO V^T - D)
  dK/dV kernel: grid (B*H, Sk/BK), loops Q tiles; dV += P^T dO,
                dK += dS^T Q
where D = rowsum(dO * O). Differentiation is wired through jax.custom_vjp,
so `jax.grad` through `attention(use_flash=True)` hits these kernels.

Used by ops.attention.attention when `use_flash=True`; the jnp
implementation remains the numerical reference. `interpret=None`
(every entry's default) means the interpreter on the cpu platform and
Mosaic on any other — ops/pallas_call.py owns that choice.

VMEM: the forward and dQ kernels keep one head's whole K and V sequence
resident (the dK/dV kernel: Q and dO), double-buffered by the pipeline.
`_vmem_params` raises the scoped-VMEM limit to the computed need when it
passes Mosaic's 16 MiB default, and refuses with FlashVmemError past
what a v5e core holds — tiling K/V through the grid instead is ROADMAP
Reach 11.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_call import pallas_call

BQ = 128  # query tile (MXU-aligned)
BK = 128  # key tile

# Mosaic's default scoped-VMEM limit, and the most this module will ask
# for: a v5e TensorCore has 128 MiB of VMEM, and the compiler needs room
# beyond the blocks this module can count
_VMEM_DEFAULT = 16 * 2**20
_VMEM_MAX = 100 * 2**20


class FlashVmemError(ValueError):
    """The whole-sequence-resident blocks of a flash kernel do not fit
    the VMEM this module is willing to request."""


def _vmem_params(what, seq, d, dtype):
    """CompilerParams for a kernel holding two (seq, d) blocks of
    `dtype` resident per grid step — K and V, or Q and dO — each
    double-buffered by the pipeline; None when the default limit is
    enough."""
    resident = 2 * 2 * seq * d * jnp.dtype(dtype).itemsize
    # tile-sized operands, f32 in-kernel temporaries (half a dozen score
    # tiles: 1 MiB each at 512 x 512), compiler scratch
    need = resident + (8 + 8 * (_tile(seq) // 256) ** 2) * 2**20
    if need <= _VMEM_DEFAULT:
        return None
    if need > _VMEM_MAX:
        raise FlashVmemError(
            f"flash attention {what}: two resident blocks of "
            f"({seq}, {d}) {jnp.dtype(dtype).name} need "
            f"{need / 2**20:.0f} MiB of VMEM, over the "
            f"{_VMEM_MAX / 2**20:.0f} MiB this kernel may request; shard "
            f"the sequence (attention_param sequence_parallel) or use "
            f"bf16")
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _tile_mask(qi, j, bq, bk, causal, sk, sk_valid, window=0):
    """Valid-score mask for the (qi, j) q x k tile, or None when every
    entry is valid. ONE definition shared by the forward and dQ kernels —
    a mask change applied to only one of them would silently desync
    gradients from the forward. causal: keys at/before the query only;
    sk_valid < sk: padded key columns (zero-filled by the wrapper) must
    not contribute (exp(0-m) != 0 in the softmax denominator; in dQ,
    p = exp(0 - lse) can overflow to inf)."""
    if not causal and sk_valid >= sk:
        return None
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = None
    if causal:
        mask = rows >= cols
        if window:  # key j visible to query i iff i - window < j <= i
            mask &= rows - cols < window
    if sk_valid < sk:
        ok = cols < sk_valid
        mask = ok if mask is None else mask & ok
    return mask


def _row_slice(i, tile, total):
    """Slice `tile` entries at tile index `i` along the LANE (last) axis
    of a stats/bias row. Mosaic must prove a dynamic lane offset is a
    multiple of 128; it can for `i * 128` but not for `i * 64`. A tile
    narrower than 128 only occurs when it is the whole row
    (_check_tiles: tile = min(128, total)), where the offset is the
    constant 0."""
    return pl.dslice(0 if tile == total else i * tile, tile)


def _n_k_tiles(sk, bk, sk_valid):
    """Key tiles worth visiting: fully-padded tiles are 100% masked —
    skipping them is free accuracy-wise."""
    return -(-sk_valid // bk) if sk_valid < sk else sk // bk


def _k_tile_range(qi, bq, bk, sk, n_k, causal, window):
    """[first, end) of the K tiles query tile `qi` can see: with causal
    none above the diagonal (and never the fully-padded trailing tiles),
    with a window none wholly before `first row - window + 1`. ONE
    definition for the forward and dQ kernels, like the mask."""
    if not causal:
        return 0, n_k
    end = jnp.minimum(jnp.minimum((qi + 1) * bq + bk - 1, sk) // bk, n_k)
    first = jnp.maximum(qi * bq - window + 1, 0) // bk if window else 0
    return first, end


def _dot(a, b, dims):
    """MXU product with float32 accumulation; the operands keep their
    type (bf16 x bf16 products are exact in float32)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, sk,
                bq, bk, sk_valid, has_bias, window=0):
    """rest = ([bias_ref,] o_ref, lse_ref). bias (1, sk) f32 adds to every
    score row — 0 for live keys, -inf for masked ones (ring attention
    uses it to mask globally-padded key positions per rotating block);
    -inf flows through the existing clamp math: s=-inf -> p=0 exactly,
    even in fully-biased-out tiles (blk_m clamps to 0 first)."""
    bias_ref, o_ref, lse_ref = rest if has_bias else (None, *rest)
    qi = pl.program_id(1)
    q = q_ref[0]  # (bq, d)
    n_k = _n_k_tiles(sk, bk, sk_valid)

    def body(j, carry):
        out, m, l = carry
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        s = _dot(q, k, ((1,), (1,))) * scale
        if has_bias:
            s = s + bias_ref[0, _row_slice(j, bk, sk)].astype(
                jnp.float32)[None, :]
        mask = _tile_mask(qi, j, bq, bk, causal, sk, sk_valid, window)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        blk_m = jnp.max(s, axis=1)
        blk_m = jnp.where(jnp.isneginf(blk_m), 0.0, blk_m)
        p = jnp.exp(s - blk_m[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        blk_l = jnp.sum(p, axis=1)
        new_m = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(blk_m - new_m)
        l = l * alpha + blk_l * beta
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))
        out = out * alpha[:, None] + pv * beta[:, None]
        return out, new_m, l

    d = q_ref.shape[-1]
    out0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    first, end = _k_tile_range(qi, bq, bk, sk, n_k, causal, window)
    out, m, l = jax.lax.fori_loop(first, end, body, (out0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (out / l_safe[:, None]).astype(o_ref.dtype)
    # logsumexp per row; backward recomputes p = exp(s - lse). m is never
    # -inf here (fully-masked blocks clamp blk_m to 0). Stored (BH, 1, S):
    # Mosaic requires the last two block dims to be (8,128)-tiled or equal
    # to the array dims — the singleton axis satisfies that where a 2D
    # (1, bq) block would not.
    lse_ref[0, 0] = (m + jnp.log(l_safe)).astype(lse_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, sk, bq, bk, sk_valid, has_bias, window=0):
    bias_ref, dq_ref = rest if has_bias else (None, *rest)
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)       # (bq,)
    delta = delta_ref[0, 0].astype(jnp.float32)   # (bq,)
    n_k = _n_k_tiles(sk, bk, sk_valid)

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        s = _dot(q, k, ((1,), (1,))) * scale
        if has_bias:
            s = s + bias_ref[0, _row_slice(j, bk, sk)].astype(
                jnp.float32)[None, :]
        p = jnp.exp(s - lse[:, None])          # normalized probabilities
        # the same mask as the forward (see _tile_mask: padded-column p
        # here can overflow to inf and NaN dQ via inf*0)
        mask = _tile_mask(qi, j, bq, bk, causal, sk, sk_valid, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        return dq + _dot(ds.astype(k.dtype), k, ((1,), (0,))) * scale

    d = q_ref.shape[-1]
    first, end = _k_tile_range(qi, bq, bk, sk, n_k, causal, window)
    dq = jax.lax.fori_loop(first, end, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, sq, bq, bk, has_bias, window=0):
    bias_ref, dk_ref, dv_ref = rest if has_bias else (None, *rest)
    ki = pl.program_id(1)
    k = k_ref[0]   # (bk, d)
    v = v_ref[0]
    n_q = sq // bq

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * bq, bq), :]
        do = do_ref[0, pl.dslice(i * bq, bq), :]
        lse = lse_ref[0, 0, _row_slice(i, bq, sq)].astype(jnp.float32)
        delta = delta_ref[0, 0, _row_slice(i, bq, sq)].astype(jnp.float32)
        s = _dot(q, k, ((1,), (1,))) * scale
        if has_bias:
            # this kernel's k block is the grid's second axis: the bias
            # slice is the ki-th tile, broadcast over q rows; -inf makes
            # p exactly 0, so masked keys get zero dK/dV
            s = s + bias_ref[0].astype(jnp.float32)[None, :]
        p = jnp.exp(s - lse[:, None])          # (bq, bk)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = rows >= cols
            if window:
                mask &= rows - cols < window
            p = jnp.where(mask, p, 0.0)
        dv = dv + _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        dk = dk + _dot(ds.astype(q.dtype), q, ((0,), (0,))) * scale
        return dk, dv

    d = k_ref.shape[-1]
    start, end = 0, n_q
    if causal:
        start = (ki * bk) // bq  # earlier Q tiles are fully masked
        if window:  # and so are those past the last key's window
            end = jnp.minimum(n_q, ((ki + 1) * bk + window - 2) // bq + 1)
    dk, dv = jax.lax.fori_loop(
        start, end, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _tile(s: int) -> int:
    """Tile length along a sequence of (padded) length `s`: the whole of a
    short one, else the largest of 512, 256, 128 that divides it. A 128 x
    128 tile leaves the MXU waiting on the loop around it (16.8 % of the
    kernels' roofline at S = 8192, PERF.md section 6, PR 27); a causal
    diagonal in 512-tiles computes 6 % more pairs than in 128-tiles."""
    if s <= BQ:
        return s
    return next((t for t in (512, 256) if s % t == 0), BQ)


def _check_tiles(sq: int, sk: int) -> tuple[int, int]:
    bq, bk = _tile(sq), _tile(sk)
    if sq % bq or sk % bk:
        raise ValueError(f"sequence lengths ({sq},{sk}) must be multiples "
                         f"of the tile sizes ({bq},{bk})")
    return bq, bk


def _pad_len(s: int, tile: int) -> int:
    """Padded length: a single short tile is legal as-is (block dims equal
    to array dims satisfy Mosaic's tiling rule); longer sequences round up
    to a tile multiple."""
    return s if s <= tile else -(-s // tile) * tile


def _sds(shape, dtype, like):
    """ShapeDtypeStruct for a pallas_call output, carrying the varying-
    axis set of `like` — under shard_map (ring attention) outputs must
    declare how they vary over mesh axes; outside it the vma set is
    empty/absent and a plain struct is produced."""
    axes = jax.typeof(like).vma
    if axes:
        return jax.ShapeDtypeStruct(shape, dtype, vma=axes)
    return jax.ShapeDtypeStruct(shape, dtype)


def _fwd_impl(q, k, v, causal, interpret, sk_valid=None, k_bias=None,
              window=0):
    """(B*H, S, D) q and (B*Hkv, S, D) k, v -> (out, lse); query row i of
    the leading axis reads key/value row i // (H / Hkv). k_bias: optional
    (1, Sk) f32 additive score bias shared by every row/head (0 live,
    -inf masked)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    bq, bk = _check_tiles(sq, sk)
    scale = 1.0 / math.sqrt(d)
    has_bias = k_bias is not None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               sk=sk, bq=bq, bk=bk,
                               sk_valid=sk if sk_valid is None else sk_valid,
                               has_bias=has_bias, window=window)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, sk), lambda i, j: (0, 0)))
        args.append(k_bias)
    return pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _sds((bh, sq, d), q.dtype, q),
            _sds((bh, 1, sq), jnp.float32, q),
        ],
        compiler_params=_vmem_params("forward", sk, d, k.dtype),
        interpret=interpret,
        name="flash_fwd",
    )(*args)


def _delta(do, out):
    """D_i = rowsum(dO * O) — cheap elementwise+reduce; XLA fuses it.
    (BH, 1, S) layout for the same Mosaic tiling reason as lse."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)[:, None, :]


def _bwd_impl(q, k, v, out, lse, do, causal, interpret, sk_valid=None,
              k_bias=None, delta=None, window=0):
    """out/lse are the GLOBAL attention output/logsumexp for these q rows
    (for plain flash that's this call's own forward; for ring attention
    each per-block call passes the ring-merged values, which makes the
    recomputed p the global probabilities restricted to the block)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    bq, bk = _check_tiles(sq, sk)
    scale = 1.0 / math.sqrt(d)
    if delta is None:
        delta = _delta(do, out)
    has_bias = k_bias is not None
    dq_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),   # q
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),   # k
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),   # v
        pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),   # do
        pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),   # lse
        pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),   # delta
    ]
    dq_args = [q, k, v, do, lse, delta]
    if has_bias:
        dq_specs.append(pl.BlockSpec((1, sk), lambda i, j: (0, 0)))
        dq_args.append(k_bias)
    dq = pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          sk=sk, bq=bq, bk=bk,
                          sk_valid=sk if sk_valid is None else sk_valid,
                          has_bias=has_bias, window=window),
        grid=(bh, sq // bq),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=_sds((bh, sq, d), q.dtype, q),
        compiler_params=_vmem_params("dQ", sk, d, k.dtype),
        interpret=interpret,
        name="flash_dq",
    )(*dq_args)
    dkv_specs = [
        pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),   # q
        pl.BlockSpec((1, bk, d), lambda i, j: (i // group, j, 0)),   # k
        pl.BlockSpec((1, bk, d), lambda i, j: (i // group, j, 0)),   # v
        pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),   # do
        pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),   # lse
        pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),   # delta
    ]
    dkv_args = [q, k, v, do, lse, delta]
    if has_bias:
        dkv_specs.append(pl.BlockSpec((1, bk), lambda i, j: (0, j)))
        dkv_args.append(k_bias)
    # with grouped heads each query head writes its own dK/dV (float32),
    # summed over the group below: Q and dO then stay resident across a
    # head's K tiles
    kv_dtype = (k.dtype, v.dtype) if group == 1 else (jnp.float32,) * 2
    dk, dv = pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          sq=sq, bq=bq, bk=bk, has_bias=has_bias,
                          window=window),
        grid=(bh, sk // bk),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), kv_dtype[0], k),
            _sds((bh, sk, d), kv_dtype[1], v),
        ],
        compiler_params=_vmem_params("dK/dV", sq, d, q.dtype),
        interpret=interpret,
        name="flash_dkv",
    )(*dkv_args)
    if group > 1:
        dk = dk.reshape(-1, group, sk, d).sum(1).astype(k.dtype)
        dv = dv.reshape(-1, group, sk, d).sum(1).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Ring-attention block API (ops/attention.py ring_flash_attention): RAW
# kernel entries with no custom_vjp — the ring owns differentiation,
# calling flash_block per K/V rotation and flash_block_bwd with the
# ring-MERGED (out, lse), which makes each block's recomputed p the
# global probabilities restricted to that block.
# ---------------------------------------------------------------------------

def flash_block(q, k, v, *, causal=False, k_bias=None, interpret=None):
    """(B*H, Sq, D) x (B*H, Sk, D) -> (normalized out, lse). k_bias:
    (1, Sk) f32, 0 for live keys / -inf for masked (padded) ones."""
    return _fwd_impl(q, k, v, causal, interpret, k_bias=k_bias)


def flash_block_bwd(q, k, v, out, lse, do, *, causal=False, k_bias=None,
                    interpret=None, delta=None):
    """Per-block backward against the GLOBAL (out, lse): returns
    (dq_partial, dk_block, dv_block). Summing dq_partial over blocks and
    routing each dk/dv block to its owner reconstructs the exact global
    gradients."""
    return _bwd_impl(q, k, v, out, lse, do, causal, interpret,
                     k_bias=k_bias, delta=delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, interpret, sk_valid, window):
    out, _ = _fwd_impl(q, k, v, causal, interpret, sk_valid, window=window)
    return out


def _flash_fwd(q, k, v, causal, interpret, sk_valid, window):
    out, lse = _fwd_impl(q, k, v, causal, interpret, sk_valid,
                         window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, sk_valid, window, res, do):
    # sk_valid reaches the dQ kernel (p at padded columns can overflow to
    # inf when lse < -88 and must be zeroed before ds @ k). The dK/dV
    # kernel needs no mask: padded Q rows carry do = 0 (the output
    # slice's cotangent) and padded K/V ROW garbage lands only in output
    # rows the wrapper slices off.
    q, k, v, out, lse = res
    return _bwd_impl(q, k, v, out, lse, do, causal, interpret, sk_valid,
                     window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False, interpret: bool | None = None,
                    window: int = 0) -> jnp.ndarray:
    """q (B, S, H, D), k,v (B, S, Hkv, D) -> (B, S, H, D). Differentiable:
    jax.grad hits the Pallas backward kernels via custom_vjp.

    Grouped heads (Hkv < H): query head n reads key/value head
    n // (H / Hkv) through the kernels' block index maps, K and V are not
    repeated. window > 0 (causal only): key j visible to query i iff
    i - window < j <= i; tiles wholly outside are not visited.

    Arbitrary sequence lengths: lengths that don't tile evenly are padded
    up to the (128, 128) q/k tile sizes — padded key columns are masked
    out of the in-kernel softmax, padded query rows are sliced off the
    output (their gradients vanish through the zero cotangent)."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key/value heads")
    if window and not causal:
        raise ValueError("a sliding window needs causal attention")
    sq_p, sk_p = _pad_len(sq, BQ), _pad_len(sk, BK)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)
    out = _flash(qt, kt, vt, causal, interpret,
                 sk if sk_p != sk else None, window)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq] if sq_p != sq else out
