"""Pallas flash-attention kernels (TPU): forward + backward.

The Pallas path of the framework: where XLA's fusion isn't enough, ops drop
to hand-written TPU kernels (the reference's analogue is its hand-written
CUDA kernels next to cuDNN ops). Attention is the canonical case — naive
attention materializes the (Sq, Sk) score matrix in HBM; these kernels keep
it in VMEM tiles with an online softmax, O(S) memory instead of O(S^2).

Layout: (B, H, S, D) inside the kernels (sequence-minor tiles), which is
what `flash_attention_heads` takes and the attention layer produces
straight from its projections; `flash_attention` accepts the framework's
(B, S, H, D) and turns it on the way in and out (XLA folds those turns
into its layout choice: they were never the cost, PERF.md section 6, PR
34).
Two head widths: queries and keys are `d` wide, values (and so the output)
`dv` wide; they differ in latent attention, whose scores run over 128 +
64 rotary lanes and whose values are 128 wide. A width that is no multiple
of 128 is one operand all the same: the block spans the whole last axis,
Mosaic pads its lanes in VMEM, and the MXU's second pass over the
contraction runs half empty (PERF.md section 6, PR 31).
Lengths are padded to a multiple of 128 (a single shorter tile is left as
it is) and tiles are 128 to 512 long (`_tile`, from the padded length
alone). Grouped heads: K and V have B*Hkv rows and query row i reads row
i // (H / Hkv) through the block index map. bf16 operands go into the MXU
as they are, accumulated in float32.

The mask has ONE definition for all three kernels (`_band` and
`_tile_runs` for a band: causal, a causal window; `_bd_valid` and
`_bd_runs` for block diffusion over a `[noisy | clean]` sequence; padded
keys in both; `_visit` walks either): tiles wholly outside it are not
visited, and the visited ones fall into contiguous runs, each either cut
by the mask's edge (the window's edge, the diagonal, a block's border, the
padded tail) or wholly inside. Only the cut runs build and apply the mask;
an inside run's body has none. `tile_counts` says how many tiles the runs
hold.

Forward grid: (B*H, Sq/BQ) with inner loops over K tiles, accumulating
(out, m, l) in VMEM scratch; it also emits the per-row logsumexp, which
the backward re-uses to recompute normalized probabilities tile-by-tile
(FlashAttention-2 style) instead of storing P:
  dQ kernel: grid (B*H, Sq/BQ), loops K tiles; dS = P * (dO V^T - D)
  dK/dV kernel: grid (B*H, Sk/BK), loops Q tiles on TRANSPOSED scores
                (keys down the sublanes): dV += P^T dO, dK += dS^T Q
                with no operand to transpose, lse and D broadcast as
                they are stored
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
import typing

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_call import pallas_call

BQ = 128  # query tile (MXU-aligned)
BK = 128  # key tile
_LANES = 128

# Mosaic's default scoped-VMEM limit, and the most this module will ask
# for: a v5e TensorCore has 128 MiB of VMEM, and the compiler needs room
# beyond the blocks this module can count
_VMEM_DEFAULT = 16 * 2**20
_VMEM_MAX = 100 * 2**20


class FlashVmemError(ValueError):
    """The whole-sequence-resident blocks of a flash kernel do not fit
    the VMEM this module is willing to request."""


def _vmem_params(what, seq, d, dv, dtype):
    """CompilerParams for a kernel holding a (seq, d) and a (seq, dv)
    block of `dtype` resident per grid step — K and V, or Q and dO — each
    double-buffered by the pipeline, lanes padded to 128; None when the
    default limit is enough."""
    lanes = sum(-(-w // _LANES) * _LANES for w in (d, dv))
    resident = 2 * seq * lanes * jnp.dtype(dtype).itemsize
    # tile-sized operands, f32 in-kernel temporaries (half a dozen score
    # tiles: 1 MiB each at 512 x 512), compiler scratch
    need = resident + (8 + 8 * (_tile(seq) // 256) ** 2) * 2**20
    if need <= _VMEM_DEFAULT:
        return None
    if need > _VMEM_MAX:
        raise FlashVmemError(
            f"flash attention {what}: resident blocks of "
            f"({seq}, {d}) and ({seq}, {dv}) {jnp.dtype(dtype).name} need "
            f"{need / 2**20:.0f} MiB of VMEM, over the "
            f"{_VMEM_MAX / 2**20:.0f} MiB this kernel may request; shard "
            f"the sequence (attention_param sequence_parallel) or use "
            f"bf16")
    return pltpu.CompilerParams(vmem_limit_bytes=need)


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


# ---------------------------------------------------------------------------
# The mask, the tiles a kernel visits and the tiles the mask leaves whole.
# ONE definition for the forward, dQ and dK/dV kernels: a change applied to
# only one of them would silently desync gradients from the forward. Each
# kernel fixes a tile on its grid axis (queries in forward and dQ, keys in
# dK/dV) and loops over tiles of the other axis; with x a score's position
# along the grid axis and y along the loop axis, the score is valid iff
# lo <= x - y < hi (and, with padded keys, its column < sk_valid).
# ---------------------------------------------------------------------------

def _band(causal, window, keys_on_grid=False):
    """(lo, hi) of the valid band lo <= x - y < hi; None is unbounded.
    Queries on the grid: x - y = row - col, causal is 0 <= row - col and a
    window row - col < window (key j visible to query i iff
    i - window < j <= i). Keys on the grid: the same predicate read as
    col - row."""
    if not causal:
        return None, None
    if keys_on_grid:
        return (1 - window if window else None), 1
    return 0, (window or None)


def _tile_runs(g, bg, bt, n_t, n_full, lo, hi):
    """(first, in_lo, in_hi, end): grid tile `g` (length bg) visits loop
    tiles [first, end) (length bt; n_t of them hold an unpadded column,
    the first n_full only such), every other one holds no valid score. The
    tiles of [in_lo, in_hi) are INSIDE the mask, every score valid; the
    mask cuts only [first, in_lo) (x - y near hi: the window's edge in
    forward and dQ, the diagonal in dK/dV) and [in_hi, end) (near lo, and
    the padded tail). x - y spans g*bg - t*bt + [-(bt - 1), bg - 1] in
    tile t and falls as t rises. `g` may be a traced scalar or an array of
    tile indices; bounds that do not depend on it stay Python ints, so an
    empty run is known before tracing."""
    x0 = g * bg
    first, in_lo, in_hi, end = 0, 0, n_full, n_t
    if lo is not None:
        end = jnp.minimum((x0 + bg - 1 - lo) // bt + 1, n_t)
        in_hi = jnp.minimum((x0 + 1 - lo) // bt, n_full)
    if hi is not None:
        first = jnp.minimum(jnp.maximum(x0 - hi + 1, 0) // bt, end)
        in_lo = jnp.clip((jnp.maximum(x0 + bg - hi, 0) + bt - 1) // bt,
                         first, end)
    if lo is not None or hi is not None:
        in_hi = jnp.clip(in_hi, in_lo, end)
    return first, in_lo, in_hi, end


# Block diffusion (arXiv:2503.09573): the sequence is `[noisy | clean]`, two
# halves of `half` positions each cut into blocks of `block`; position i has
# pos(i) = i - half in the clean half and i in the noisy one, and blk(i) =
# pos(i) // block. Row i sees column j iff
#   both noisy: blk(j) == blk(i);    noisy row, clean column: blk(j) < blk(i);
#   both clean: blk(j) <= blk(i);    clean row, noisy column: never.
# Positions past the two halves (padding) count as clean ones. The queries
# may be the noisy half alone (rows 0..half-1 against all 2 x half keys):
# its rows are the same rows, and no query tile holds a clean row.

def _bd_valid(rows, cols, half, block):
    """The block-diffusion mask of integer positions `rows` against `cols`
    (broadcast against each other). One integer division a position; a
    score costs two compares: a row sees the clean columns whose block is
    at most `t` and the noisy ones whose block is `n`."""
    def blocks(idx):
        clean = idx >= half
        return clean, jax.lax.div(jnp.where(clean, idx - half, idx),
                                  jnp.int32(block))
    r_clean, r_blk = blocks(rows)
    c_clean, c_blk = blocks(cols)
    t = jnp.where(r_clean, r_blk, r_blk - 1)
    n = jnp.where(r_clean, -1, r_blk)
    # a noisy column never passes the first compare, a clean one never the
    # second
    among_clean = jnp.where(c_clean, c_blk, jnp.int32(2**30))
    among_noisy = jnp.where(c_clean, -2, c_blk)
    return (among_clean <= t) | (among_noisy == n)


def _span_tiles(span, bt, start, n_t, n_full):
    """(first, in_lo, in_hi, end) as `_tile_runs` gives them, of the loop
    positions [vis_lo, vis_hi) a grid tile sees, of which every position of
    the tile sees [all_lo, all_hi); no tile before `start`, and an empty
    span ends where it starts, so that it hides no tile from the next.
    Positions are never negative, so `lax.div` divides them: `//` on
    signed integers adds a sign correction that Mosaic lowers at a cost of
    its own, dozens of times a kernel (set-up time, not step time)."""
    _div = jax.lax.div
    vis_lo, vis_hi, all_lo, all_hi = span
    seen = vis_hi > vis_lo
    end = jnp.where(seen, jnp.clip(_div(vis_hi + bt - 1, bt), start, n_t),
                    start)
    first = jnp.where(seen, jnp.clip(_div(vis_lo, bt), start, end), end)
    in_lo = jnp.clip(_div(all_lo + bt - 1, bt), first, end)
    in_hi = jnp.clip(jnp.minimum(_div(all_hi, bt), n_full), in_lo, end)
    return first, in_lo, in_hi, end


def _bd_runs(g, bg, bt, n_t, n_full, half, block, keys_on_grid):
    """[(a, b, cut)]: grid tile `g` visits the loop tiles of each [a, b) in
    turn; a run with `cut` holds tiles the block-diffusion mask cuts, one
    without it tiles wholly inside. The loop positions a grid tile sees
    are two spans, the first among the noisy positions and the second among
    the clean ones, each a run of cut tiles, one of inside tiles and one of
    cut tiles again (`_span_tiles`); the spans' ends come from the blocks
    of the tile's first and last position in each half (blk is monotone
    inside a half). A run that the shapes alone leave empty is left out: a
    tile lies inside one block only if the block is that long, a span that
    ends where the half does ends on a tile's edge if the half does, and
    queries that end with the noisy half hold no clean row."""
    L, B = half, block
    _div = jax.lax.div      # of positions, as in `_span_tiles`
    x0 = g * bg
    x1 = x0 + bg - 1
    noisy, clean = x0 < L, x1 >= L          # the halves the tile touches
    n0, n1 = _div(x0, B), _div(jnp.minimum(x1, L - 1), B)  # noisy blocks
    # its clean blocks (0 where it touches no clean position)
    c0, c1 = _div(jnp.maximum(x0, L) - L, B), _div(jnp.maximum(x1, L) - L, B)
    one_block = noisy & ~clean & (n0 == n1) if B >= bt else False
    own_lo, own_hi = n0 * B, jnp.minimum((n1 + 1) * B, L)
    aligned = L % bt == 0
    if not keys_on_grid:
        # noisy columns: the blocks of the tile's noisy rows, seen by every
        # row only if they are one block. Clean columns: a noisy row sees
        # the blocks before its own, a clean row those up to its own
        lo, hi = jnp.where(noisy, own_lo, 0), jnp.where(noisy, own_hi, 0)
        seen = jnp.maximum(jnp.where(noisy, n1 * B, 0),
                           jnp.where(clean, (c1 + 1) * B, 0))
        by_all = jnp.minimum(jnp.where(noisy, n0 * B, seen),
                             jnp.where(clean, (c0 + 1) * B, seen))
        spans = [((lo, hi, jnp.where(one_block, lo, hi), hi),
                  (True, B >= bt, B >= bt)),
                 ((L, L + seen, L, L + by_all), (not aligned, True, True))]
    else:
        # noisy rows: the block of a noisy column, the blocks after a clean
        # column's. Clean rows: a clean column's block and every later one.
        # A tile that touches both halves is seen whole only by the rows of
        # its noisy columns' one block, where that block comes after its
        # clean columns' blocks, and by no clean row
        end = n_t * bt
        lo = jnp.minimum(jnp.minimum(jnp.where(noisy, own_lo, L),
                                     jnp.where(clean, (c0 + 1) * B, L)), L)
        hi = jnp.where(clean, L, own_hi)
        all_lo = jnp.where(
            noisy & clean, jnp.where((n0 == n1) & (c1 < n1), own_lo, hi),
            jnp.where(one_block, lo,
                      jnp.where(clean, jnp.minimum((c1 + 1) * B, L), hi)))
        only_clean = clean & ~noisy
        spans = [((lo, hi, all_lo, hi),
                  (True, True, not (aligned and (B < bt or B % bt == 0))))]
        if end > L:
            # queries that hold clean rows (with keys, or past the noisy
            # half where they are padding)
            spans.append(((jnp.where(clean, L + c0 * B, 0),
                           jnp.where(clean, end, 0),
                           jnp.where(only_clean, L + c1 * B,
                                     jnp.where(clean, end, 0)),
                           jnp.where(clean, end, 0)), (True, True, False)))
    runs, start = [], 0
    for span, (lead, inside, tail) in spans:
        first, in_lo, in_hi, end = _span_tiles(span, bt, start, n_t, n_full)
        runs += ([(first, in_lo, True)] * lead + [(in_lo, in_hi, False)]
                 * inside + [(in_hi, end, True)] * tail)
        start = end
    return runs


def _runs(g, bg, bt, n_t, n_full, band, bd, keys_on_grid):
    """[(a, b, cut)] of either mask: the band's three runs (window's edge,
    inside, diagonal or padded tail) or the block-diffusion mask's."""
    if bd is not None:
        return _bd_runs(g, bg, bt, n_t, n_full, *bd, keys_on_grid)
    first, in_lo, in_hi, end = _tile_runs(g, bg, bt, n_t, n_full, *band)
    return [(first, in_lo, True), (in_lo, in_hi, False), (in_hi, end, True)]


def _visit(g, tile, *, bg, bt, n_t, band, y_valid=None, bd=None,
           keys_on_grid=False):
    """tile(t, mask) over the loop tiles grid tile `g` sees, in rising t.
    `mask` is the (bg, bt) valid-score mask, grid axis first, on the tiles
    it cuts and None on an inside run, whose body then holds no iota, no
    compare and no select; an empty run costs its bounds check. `tile`
    accumulates into VMEM scratch: values carried from one run's loop into
    the next are moved register by register at every seam (PERF.md section
    6, PR 30). y_valid: valid positions along a padded loop axis (key
    columns zero-filled by the wrapper: exp(0 - m) != 0 in the softmax
    denominator; in dQ, p = exp(0 - lse) can overflow to inf). bd: (half,
    block) of the block-diffusion mask, which then stands where the band
    does; keys_on_grid: the grid axis holds the mask's columns."""
    lo, hi = band
    n_full = n_t if y_valid is None else y_valid // bt
    runs = _runs(g, bg, bt, n_t, n_full, band, bd, keys_on_grid)

    def cut(t, _):
        y = jax.lax.broadcasted_iota(jnp.int32, (bg, bt), 1)
        if bd is None:
            # x - y is the same iota difference on every tile plus the
            # scalar g*bg - t*bt: each bound is one compare with a scalar
            diff = jax.lax.broadcasted_iota(jnp.int32, (bg, bt), 0) - y
            off = g * bg - t * bt
            conds = [diff >= lo - off] if lo is not None else []
            if hi is not None:
                conds.append(diff < hi - off)
        else:
            # blocks from one column and one row of positions, then two
            # compares a score
            x = g * bg + jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)
            z = t * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
            conds = [_bd_valid(z, x, *bd) if keys_on_grid
                     else _bd_valid(x, z, *bd)]
        if n_full < n_t:
            conds.append(y < y_valid - t * bt)
        tile(t, functools.reduce(jnp.logical_and, conds))

    for a, b, is_cut in runs:
        if not (isinstance(a, int) and isinstance(b, int) and a >= b):
            jax.lax.fori_loop(a, b, cut if is_cut
                              else lambda t, _: tile(t, None), None)


def tile_counts(sq, sk, causal, window=0, sk_valid=None, *, dkv=False,
                bd=None):
    """(visited, inside): the tiles one head's forward (and dQ) kernel
    visits over (padded) lengths sq x sk, and those of them it runs
    without the mask — with dkv=True the dK/dV kernel's, which sees no
    key padding; with bd = (half, block) under the block-diffusion mask. A
    pure function of shape, from the range code the kernels run: how often
    the unmasked body engages."""
    bq, bk = _check_tiles(sq, sk)
    if dkv:
        g, shape = jnp.arange(sk // bk), (bk, bq, sq // bq, sq // bq)
    else:
        valid = sk if sk_valid is None else sk_valid
        g = jnp.arange(sq // bq)
        shape = (bq, bk, _n_k_tiles(sk, bk, valid), valid // bk)
    runs = _runs(g, *shape, _band(causal, window, keys_on_grid=dkv), bd,
                 dkv)
    tiles = lambda spans: int(sum(jnp.sum(jnp.broadcast_to(b - a, g.shape))
                                  for a, b in spans))
    return (tiles((a, b) for a, b, _ in runs),
            tiles((a, b) for a, b, cut in runs if not cut))


def _lanes(x, n):
    """A (rows, _LANES) row statistic, every lane of a row the same value,
    as (rows, n) beside a (rows, n) block."""
    if n == _LANES:
        return x
    return x[:, :n] if n < _LANES else jnp.broadcast_to(x[:, :1],
                                                        (x.shape[0], n))


def _dot(a, b, dims):
    """MXU product with float32 accumulation; the operands keep their
    type (bf16 x bf16 products are exact in float32)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, sk,
                bq, bk, sk_valid, has_bias, window=0, bd=None):
    """rest = ([bias_ref,] o_ref, lse_ref, then the scratch accumulators
    acc_ref (bq, dv), m_ref and l_ref (bq, _LANES), a row's running maximum
    and sum in every lane). bias (1, sk) f32 adds to every
    score row — 0 for live keys, -inf for masked ones (ring attention
    uses it to mask globally-padded key positions per rotating block);
    -inf flows through the existing clamp math: s=-inf -> p=0 exactly,
    even in fully-biased-out tiles (blk_m clamps to 0 first). The tile
    mask takes the same road: s = -inf where it cuts."""
    *rest, acc_ref, m_ref, l_ref = rest
    bias_ref, o_ref, lse_ref = rest if has_bias else (None, *rest)
    qi = pl.program_id(1)
    q = q_ref[0]  # (bq, d)
    d = acc_ref.shape[-1]   # the values' width
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    def tile(j, mask):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        s = _dot(q, k, ((1,), (1,))) * scale
        if has_bias:
            s = s + bias_ref[0, _row_slice(j, bk, sk)].astype(
                jnp.float32)[None, :]
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        blk_m = jnp.max(s, axis=1, keepdims=True)
        blk_m = jnp.where(jnp.isneginf(blk_m), 0.0, blk_m)
        p = jnp.exp(s - blk_m)
        blk_l = jnp.sum(p, axis=1, keepdims=True)
        m = m_ref[...]
        new_m = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(blk_m - new_m)
        m_ref[...] = new_m
        l_ref[...] = l_ref[...] * alpha + blk_l * beta
        pv = _dot(p.astype(v.dtype), v, ((1,), (0,)))
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, d)
                        + pv * _lanes(beta, d))

    _visit(qi, tile, bg=bq, bt=bk, n_t=_n_k_tiles(sk, bk, sk_valid),
           band=_band(causal, window), bd=bd,
           y_valid=sk_valid if sk_valid < sk else None)
    m, l = m_ref[...], l_ref[...]
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc_ref[...] / _lanes(l_safe, d)).astype(o_ref.dtype)
    # logsumexp per row; backward recomputes p = exp(s - lse). m is never
    # -inf here (fully-masked blocks clamp blk_m to 0). Stored (BH, 1, S):
    # Mosaic requires the last two block dims to be (8,128)-tiled or equal
    # to the array dims — the singleton axis satisfies that where a 2D
    # (1, bq) block would not.
    lse_ref[0, 0] = (m + jnp.log(l_safe))[:, 0].astype(lse_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, sk, bq, bk, sk_valid, has_bias, window=0,
                   bd=None):
    *rest, acc_ref = rest
    bias_ref, dq_ref = rest if has_bias else (None, *rest)
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0].astype(jnp.float32)       # (bq,)
    delta = delta_ref[0, 0].astype(jnp.float32)   # (bq,)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(j, mask):
        k = k_ref[0, pl.dslice(j * bk, bk), :]
        v = v_ref[0, pl.dslice(j * bk, bk), :]
        s = _dot(q, k, ((1,), (1,))) * scale
        if has_bias:
            s = s + bias_ref[0, _row_slice(j, bk, sk)].astype(
                jnp.float32)[None, :]
        p = jnp.exp(s - lse[:, None])          # normalized probabilities
        if mask is not None:  # padded-column p can be inf: NaN via inf*0
            p = jnp.where(mask, p, 0.0)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        acc_ref[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,))) * scale

    _visit(qi, tile, bg=bq, bt=bk, n_t=_n_k_tiles(sk, bk, sk_valid),
           band=_band(causal, window), bd=bd,
           y_valid=sk_valid if sk_valid < sk else None)
    dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, sq, bq, bk, has_bias, window=0, bd=None):
    *rest, dk_acc, dv_acc = rest
    bias_ref, dk_ref, dv_ref = rest if has_bias else (None, *rest)
    ki = pl.program_id(1)
    k = k_ref[0]   # (bk, d)
    v = v_ref[0]
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    if has_bias:
        # this kernel's k block is the grid's second axis: the bias slice
        # is the ki-th tile, one value a key row; -inf makes p exactly 0,
        # so masked keys get zero dK/dV
        bias = bias_ref[0].astype(jnp.float32)[:, None]

    def tile(i, mask):
        # scores transposed, keys down the sublanes: the per-query lse and
        # delta rows broadcast as they are stored and no product needs a
        # transposed operand
        q = q_ref[0, pl.dslice(i * bq, bq), :]
        do = do_ref[0, pl.dslice(i * bq, bq), :]
        lse = lse_ref[0, 0, _row_slice(i, bq, sq)].astype(jnp.float32)
        delta = delta_ref[0, 0, _row_slice(i, bq, sq)].astype(jnp.float32)
        s = _dot(k, q, ((1,), (1,))) * scale    # (bk, bq)
        if has_bias:
            s = s + bias
        p = jnp.exp(s - lse[None, :])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_acc[...] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        dp = _dot(v, do, ((1,), (1,)))
        ds = p * (dp - delta[None, :])
        dk_acc[...] += _dot(ds.astype(q.dtype), q, ((1,), (0,))) * scale

    _visit(ki, tile, bg=bk, bt=bq, n_t=sq // bq,
           band=_band(causal, window, keys_on_grid=True), bd=bd,
           keys_on_grid=True)
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


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


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct for a pallas_call output, carrying the varying-
    axis set `vma` of the operands — under shard_map (ring attention)
    outputs must declare how they vary over mesh axes; outside it the set
    is empty and a plain struct is produced."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


class _Static(typing.NamedTuple):
    """What a kernel's program depends on beside its flags: shapes, types,
    mesh axes. Hashable: the `_*_call` builders below are memoized on it,
    so that jax traces each distinct kernel once a process and not once
    a layer and program (a model's layers mostly share one; at 0.1 s a
    trace, the benchmark's 28 calls were 4 s of every start)."""
    bh: int
    group: int      # query heads a key/value head
    sq: int
    sk: int
    d: int          # width of a query and a key
    dv: int         # width of a value, and of the output
    q_dtype: jnp.dtype
    k_dtype: jnp.dtype
    v_dtype: jnp.dtype
    vma: frozenset  # mesh axes the operands vary over (shard_map)
    sk_valid: int

    @classmethod
    def of(cls, q, k, v, sk_valid):
        bh, sq, d = q.shape
        sk = k.shape[1]
        return cls(bh, bh // k.shape[0], sq, sk, d, v.shape[2], q.dtype,
                   k.dtype, v.dtype, frozenset(jax.typeof(q).vma),
                   sk if sk_valid is None else sk_valid)


@functools.lru_cache(maxsize=None)
def _fwd_call(st: _Static, causal, window, has_bias, interpret, bd=None):
    bh, group, sq, sk, d, dv = st.bh, st.group, st.sq, st.sk, st.d, st.dv
    bq, bk = _check_tiles(sq, sk)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, sk=sk, bq=bq, bk=bk,
                               sk_valid=st.sk_valid, has_bias=has_bias,
                               window=window, bd=bd)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),
        pl.BlockSpec((1, sk, dv), lambda i, j: (i // group, 0, 0)),
    ]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, sk), lambda i, j: (0, 0)))
    return pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _sds((bh, sq, dv), st.q_dtype, st.vma),
            _sds((bh, 1, sq), jnp.float32, st.vma),
        ],
        scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),       # out
                        pltpu.VMEM((bq, _LANES), jnp.float32),   # m
                        pltpu.VMEM((bq, _LANES), jnp.float32)],  # l
        compiler_params=_vmem_params("forward", sk, d, dv, st.k_dtype),
        interpret=interpret,
        name="flash_fwd",
    )


def _fwd_impl(q, k, v, causal, interpret, sk_valid=None, k_bias=None,
              window=0, bd=None):
    """(B*H, S, D) q and (B*Hkv, S, D) k, v -> (out, lse); query row i of
    the leading axis reads key/value row i // (H / Hkv). k_bias: optional
    (1, Sk) f32 additive score bias shared by every row/head (0 live,
    -inf masked)."""
    bias = () if k_bias is None else (k_bias,)
    return _fwd_call(_Static.of(q, k, v, sk_valid), causal, window,
                     bool(bias), interpret, bd)(q, k, v, *bias)


def _delta(do, out):
    """D_i = rowsum(dO * O) — cheap elementwise+reduce; XLA fuses it.
    (BH, 1, S) layout for the same Mosaic tiling reason as lse."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)[:, None, :]


@functools.lru_cache(maxsize=None)
def _dq_call(st: _Static, causal, window, has_bias, interpret, bd=None):
    bh, group, sq, sk, d, dv = st.bh, st.group, st.sq, st.sk, st.d, st.dv
    bq, bk = _check_tiles(sq, sk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),   # q
        pl.BlockSpec((1, sk, d), lambda i, j: (i // group, 0, 0)),   # k
        pl.BlockSpec((1, sk, dv), lambda i, j: (i // group, 0, 0)),  # v
        pl.BlockSpec((1, bq, dv), lambda i, j: (i, j, 0)),  # do
        pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),   # lse
        pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j)),   # delta
    ]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, sk), lambda i, j: (0, 0)))
    return pallas_call(
        functools.partial(_bwd_dq_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, sk=sk, bq=bq, bk=bk,
                          sk_valid=st.sk_valid, has_bias=has_bias,
                          window=window, bd=bd),
        grid=(bh, sq // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=_sds((bh, sq, d), st.q_dtype, st.vma),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_vmem_params("dQ", sk, d, dv, st.k_dtype),
        interpret=interpret,
        name="flash_dq",
    )


@functools.lru_cache(maxsize=None)
def _dkv_call(st: _Static, causal, window, has_bias, interpret, bd=None):
    bh, group, sq, sk, d, dv = st.bh, st.group, st.sq, st.sk, st.d, st.dv
    bq, bk = _check_tiles(sq, sk)
    in_specs = [
        pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),   # q
        pl.BlockSpec((1, bk, d), lambda i, j: (i // group, j, 0)),   # k
        pl.BlockSpec((1, bk, dv), lambda i, j: (i // group, j, 0)),  # v
        pl.BlockSpec((1, sq, dv), lambda i, j: (i, 0, 0)),  # do
        pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),   # lse
        pl.BlockSpec((1, 1, sq), lambda i, j: (i, 0, 0)),   # delta
    ]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bk), lambda i, j: (0, j)))
    # with grouped heads each query head writes its own dK/dV (float32),
    # summed over the group by the caller: Q and dO then stay resident
    # across a head's K tiles
    kv_dtype = (st.k_dtype, st.v_dtype) if group == 1 else (jnp.float32,) * 2
    return pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, sq=sq, bq=bq, bk=bk,
                          has_bias=has_bias, window=window, bd=bd),
        grid=(bh, sk // bk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), kv_dtype[0], st.vma),
            _sds((bh, sk, dv), kv_dtype[1], st.vma),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=_vmem_params("dK/dV", sq, d, dv, st.q_dtype),
        interpret=interpret,
        name="flash_dkv",
    )


def _bwd_impl(q, k, v, out, lse, do, causal, interpret, sk_valid=None,
              k_bias=None, delta=None, window=0, bd=None):
    """out/lse are the GLOBAL attention output/logsumexp for these q rows
    (for plain flash that's this call's own forward; for ring attention
    each per-block call passes the ring-merged values, which makes the
    recomputed p the global probabilities restricted to the block)."""
    if delta is None:
        delta = _delta(do, out)
    st = _Static.of(q, k, v, sk_valid)
    args = (q, k, v, do, lse, delta, *(() if k_bias is None else (k_bias,)))
    flags = (causal, window, k_bias is not None, interpret, bd)
    dq = _dq_call(st, *flags)(*args)
    dk, dv = _dkv_call(st, *flags)(*args)
    if st.group > 1:
        dk = dk.reshape(-1, st.group, st.sk, st.d).sum(1).astype(k.dtype)
        dv = dv.reshape(-1, st.group, st.sk, st.dv).sum(1).astype(v.dtype)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, interpret, sk_valid, window, bd):
    out, _ = _fwd_impl(q, k, v, causal, interpret, sk_valid, window=window,
                       bd=bd)
    return out


# Under an Attention layer's `remat: true` these two residuals are kept
# (`AttentionLayer.kept_under_remat`) and the forward kernel is not run
# again in the backward pass: what is computed again is what leads up to
# q, k and v. Without a checkpoint around the call the names do nothing.
KEPT_UNDER_REMAT = ("flash.out", "flash.lse")


def _flash_fwd(q, k, v, causal, interpret, sk_valid, window, bd):
    out, lse = _fwd_impl(q, k, v, causal, interpret, sk_valid,
                         window=window, bd=bd)
    out, lse = (checkpoint_name(x, name)
                for x, name in zip((out, lse), KEPT_UNDER_REMAT))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, sk_valid, window, bd, res, do):
    # sk_valid reaches the dQ kernel (p at padded columns can overflow to
    # inf when lse < -88 and must be zeroed before ds @ k). The dK/dV
    # kernel needs no mask: padded Q rows carry do = 0 (the output
    # slice's cotangent) and padded K/V ROW garbage lands only in output
    # rows the wrapper slices off.
    q, k, v, out, lse = res
    return _bwd_impl(q, k, v, out, lse, do, causal, interpret, sk_valid,
                     window=window, bd=bd)


_flash.defvjp(_flash_fwd, _flash_bwd)


def check_block_diffusion(block: int, sq: int, sk: int, causal, window):
    """Refuse what the block-diffusion mask has no meaning with."""
    if block and (causal or window or block < 0 or sk % 2
                  or sq not in (sk, sk // 2)):
        raise ValueError(
            f"block diffusion (block length {block}) is a mask of its own "
            f"over one [noisy | clean] sequence of even length: neither "
            f"causal nor window, the keys the whole sequence and the "
            f"queries the whole or its noisy half (got causal "
            f"{bool(causal)}, window {window}, lengths {sq} and {sk})")


def flash_attention_heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          causal: bool = False,
                          interpret: bool | None = None, window: int = 0,
                          block_diffusion: int = 0) -> jnp.ndarray:
    """The kernels' own order: q (B, H, S, D), k (B, Hkv, S, D), v (B,
    Hkv, S, Dv) -> (B, H, S, Dv); scores are scaled by 1 / sqrt(D). What a
    layer that produces its heads in this order (layers/sequence.py) calls,
    so that nothing is re-laid out between its projections and the
    kernels: folding B into H is a view. Differentiable: jax.grad hits the
    Pallas backward kernels via custom_vjp.

    Grouped heads (Hkv < H): query head n reads key/value head
    n // (H / Hkv) through the kernels' block index maps, K and V are not
    repeated. window > 0 (causal only): key j visible to query i iff
    i - window < j <= i; tiles wholly outside are not visited.
    block_diffusion > 0 (neither causal nor a window): the keys are
    `[noisy | clean]`, two halves of Sk / 2 in blocks of that length, under
    the block-diffusion mask (`_bd_valid`); the queries are the same
    sequence or its noisy half alone (Sq = Sk / 2), whose rows then see
    what they see in the whole.

    Arbitrary sequence lengths: a length over 128 is padded up to a
    multiple of 128 and walked in tiles of 128 to 512 (`_tile`) — padded
    key columns are masked out of the in-kernel softmax, padded query rows
    are sliced off the output (their gradients vanish through the zero
    cotangent)."""
    b, h, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[3]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key/value heads")
    if window and not causal:
        raise ValueError("a sliding window needs causal attention")
    check_block_diffusion(block_diffusion, sq, sk, causal, window)
    bd = (sk // 2, block_diffusion) if block_diffusion else None
    sq_p, sk_p = _pad_len(sq, BQ), _pad_len(sk, BK)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    out = _flash(q.reshape(b * h, sq_p, d), k.reshape(b * hkv, sk_p, d),
                 v.reshape(b * hkv, sk_p, dv), causal, interpret,
                 sk if sk_p != sk else None, window, bd)
    out = out.reshape(b, h, sq_p, dv)
    return out[:, :, :sq] if sq_p != sq else out


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    **kw) -> jnp.ndarray:
    """`flash_attention_heads` for the framework's order: q (B, S, H, D),
    k (B, S, Hkv, D), v (B, S, Hkv, Dv) -> (B, S, H, Dv), turned head-major
    on the way in and back on the way out (what
    `ops.attention.attention(use_flash=True)` calls)."""
    out = flash_attention_heads(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                                **kw)
    return out.transpose(0, 2, 1, 3)
