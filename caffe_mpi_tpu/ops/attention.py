# lint: ok(reference-citation) — TPU-native op: the CNN-era reference has
# no attention kernel to cite; SURVEY §5.7 records the design decision
"""Attention + ring attention (sequence/context parallelism).

The reference is a CNN-era framework with no attention op (SURVEY §5.7),
but this framework treats long-context and distributed execution as
first-class: the mesh carries a sequence-parallel story from day one.

- `attention`: standard multi-head scaled-dot-product attention on one
  device, (B, S, H, D) layout, optional causal mask. XLA maps the two
  batched matmuls straight onto the MXU.
- `ring_attention`: the same computation with the SEQUENCE axis sharded
  over a mesh axis. Each device owns one Q/K/V shard; K/V shards rotate
  around the ring with `lax.ppermute` while a numerically-stable online
  softmax (flash-attention style running max/sum) accumulates partial
  results — sequence length scales with the number of devices at O(S/n)
  memory per device, and the ppermute traffic rides the ICI ring.

Layout note: (batch, seq, heads, head_dim); collectives run under
`shard_map` with the seq axis mapped to a mesh axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.mesh import mark_varying


def _block_attn(q, k, v, *, scale, mask=None):
    """One q-block x k-block attention with running-softmax stats.

    q: (B,Sq,H,D), k/v: (B,Sk,H,D). Returns (out_unnorm, row_max, row_sum)
    where out_unnorm = sum_j exp(s_ij - row_max) v_j."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                      # (B,H,Sq)
    # guard fully-masked rows (exp(-inf - -inf)); contribute zeros
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                      # (B,H,Sq)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out, m_safe, l


def rope_tables(s: int, d: int, theta: float, *, period: int = 0,
                pairs: bool = False, lead: int = 0):
    """(cos, sin) float32 (S, lead + d) of the rotary turn `turn_lanes`
    applies over the last `d` lanes of a head: positions 0..S-1 (row i at
    i mod `period` where one is given: the two halves of a block-diffusion
    sequence sit at the same positions), no scaling. Rotate-half
    convention: lane i of the first half and lane i of the second share
    the angle a_i = pos * theta^(-i / (d/2)); `pairs` (`rope_interleave`):
    lanes 2i and 2i+1 share a_i = pos * theta^(-2i / d). The sign of the
    partner's term is folded into `sin` (minus on the lane whose partner
    lies above it); the first `lead` lanes are not turned: cos 1, sin 0.
    Built once a layer call and handed to every tensor it turns."""
    half = d // 2
    pos = jnp.arange(s)
    if period:
        pos = pos % period
    ang = (pos.astype(jnp.float32)[:, None]
           * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)[None, :])
    both = ((lambda t: jnp.repeat(t, 2, axis=-1)) if pairs
            else (lambda t: jnp.concatenate([t, t], -1)))
    cos, sin = both(jnp.cos(ang)), both(jnp.sin(ang))
    sin = jnp.where(_partner_above(d, d, pairs), -sin, sin)
    if lead:
        cos = jnp.concatenate([jnp.ones((s, lead), jnp.float32), cos], -1)
        sin = jnp.concatenate([jnp.zeros((s, lead), jnp.float32), sin], -1)
    return cos, sin


def _partner_above(width: int, d: int, pairs: bool):
    """(width,) bool: the lanes, of the last `d`, whose partner in the
    rotary turn is a higher lane (the even one of a pair, the first half)."""
    lane = jnp.arange(width) - (width - d)
    return lane % 2 == 0 if pairs else lane < d // 2


def lane_partner(x: jnp.ndarray, d: int, pairs: bool = False):
    """x with each of its last `d` lanes replaced by its partner in the
    rotary turn (x_i+d/2 and x_i-d/2, or the other lane of an adjacent
    pair), zero on the lanes before them (their `sin` is 0). A product
    with a 0/1 matrix: exact in any type (one term a sum), and the one form
    of a lane permutation that XLA:TPU fuses with what reads it, forward
    and backward; `jnp.roll`'s slices and concatenation cost a pass of
    their own over the tensor each way (PERF.md section 6, PR 34)."""
    width = x.shape[-1]
    lane = jnp.arange(width)
    shift = 1 if pairs else d // 2
    partner = jnp.where(_partner_above(width, d, pairs), lane + shift,
                        lane - shift)
    swap = ((lane[:, None] == partner[None, :])
            & (lane >= width - d)[None, :]).astype(x.dtype)
    return jnp.einsum("...d,de->...e", x, swap,
                      precision=lax.Precision.HIGHEST)


def turn_lanes(x: jnp.ndarray, partner: jnp.ndarray, cos, sin, *,
               head_major: bool = False) -> jnp.ndarray:
    """x * cos + partner * sin: the rotary turn of x (B, S, H, D), or (B,
    H, S, D) with `head_major`, by `rope_tables`' (S, D) tables; `partner`
    is `lane_partner(x, ...)`. The multiply-add in float32, one rounding
    to x's type at the end."""
    if not head_major:
        cos, sin = cos[:, None, :], sin[:, None, :]
    out = x.astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin
    return out.astype(x.dtype)


def rope(x: jnp.ndarray, theta: float, period: int = 0) -> jnp.ndarray:
    """Rotary positions over the whole head, rotate-half convention:
    x (B, S, H, D) -> the same (`rope_tables`, `turn_lanes`).
    With x = [x1, x2] (halves of D) and a_i = pos * theta^(-i / (D/2)):
    [x1 cos a - x2 sin a, x2 cos a + x1 sin a]."""
    s, d = x.shape[1], x.shape[-1]
    return turn_lanes(x, lane_partner(x, d),
                      *rope_tables(s, d, theta, period=period))


def rope_pairs(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions over the whole of x's last axis, adjacent-pair
    convention (`rope_interleave`): (x_2i, x_2i+1) turned by the angle
    a_i = pos * theta^(-2i / D), positions 0..S-1, no scaling:
    [x_2i cos a_i - x_2i+1 sin a_i, x_2i+1 cos a_i + x_2i sin a_i], each in
    its own lane. x (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    return turn_lanes(x, lane_partner(x, d, pairs=True),
                      *rope_tables(s, d, theta, pairs=True))


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = False, use_flash: bool = False,
              flash_interpret: bool | None = None,
              window: int = 0, block_diffusion: int = 0) -> jnp.ndarray:
    """Single-device attention: q (B,S,H,D), k (B,S,Hkv,D), v (B,S,Hkv,Dv)
    -> (B,S,H,Dv), scores scaled by 1 / sqrt(D); Dv is D everywhere but in
    latent attention.

    Grouped heads: with Hkv < H (H a multiple of it) query head n reads
    key/value head n // (H / Hkv). window > 0 (with causal): key j is
    visible to query i iff i - window < j <= i. block_diffusion > 0
    (neither causal nor a window): q, k and v are one `[noisy | clean]`
    sequence, two halves of S / 2 in blocks of that length, and row i sees
    column j under the block-diffusion mask (flash_attention.py
    `_bd_valid`: inside its block among the noisy, the blocks before its
    own among the clean; a clean row the clean blocks up to its own).

    use_flash: route through the Pallas flash-attention kernels
    (ops/flash_attention.py) — O(S) memory VMEM-tiled online softmax,
    differentiable (custom_vjp backward kernels); arbitrary sequence
    lengths (uneven lengths are padded to the kernel tile sizes and
    masked). flash_interpret: None = the interpreter on the cpu
    platform, Mosaic on any other (ops/pallas_call.py); a bool pins
    it."""
    if window and not causal:
        raise ValueError("a sliding window needs causal attention")
    if use_flash or block_diffusion:
        from . import flash_attention as flash
        flash.check_block_diffusion(block_diffusion, q.shape[1], k.shape[1],
                                    causal, window)
    if use_flash:
        return flash.flash_attention(q, k, v, causal=causal, window=window,
                                     block_diffusion=block_diffusion,
                                     interpret=flash_interpret)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        if window:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), -window)
        mask = mask[None, None]
    if block_diffusion:
        at = jnp.arange(q.shape[1], dtype=jnp.int32)
        mask = flash._bd_valid(at[:, None], at[None, :], q.shape[1] // 2,
                               block_diffusion)[None, None]
    out, m, l = _block_attn(q, k, v, scale=scale, mask=mask)
    return out / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis_name: str, causal: bool = False,
                   valid_len: int | None = None) -> jnp.ndarray:
    """Sequence-parallel attention inside shard_map.

    q,k,v: the LOCAL sequence shard (B, S/n, H, D) on each device of the
    `axis_name` mesh axis. Returns the local output shard. K/V blocks make
    one full trip around the ring (n-1 ppermutes), overlapping compute with
    neighbor transfers — the TPU-native equivalent of all-gather-free
    context parallelism.

    valid_len: global key positions >= valid_len are padding (the top-level
    wrapper pads uneven sequence lengths up to a multiple of the ring
    size); they are masked out of every block."""
    n_dev = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(q.shape[-1])
    block_len = q.shape[1]
    b, s, h, d = q.shape

    def step(carry, i):
        out, m, l, kk, vv = carry
        src_idx = (my_idx + i) % n_dev
        mask = None
        a = jnp.arange(block_len)[:, None]
        bcol = jnp.arange(block_len)[None, :]
        if causal:
            mask = ((my_idx * block_len + a) >= (src_idx * block_len + bcol))
        if valid_len is not None:
            key_ok = (src_idx * block_len + bcol) < valid_len
            mask = key_ok if mask is None else (mask & key_ok)
        if mask is not None:
            mask = jnp.broadcast_to(mask, (block_len, block_len))[None, None]
        blk_out, blk_m, blk_l = _block_attn(q, kk, vv, scale=scale, mask=mask)
        # online-softmax merge of (out, m, l) with the new block
        new_m = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - new_m)      # rescale old accumulation
        beta = jnp.exp(blk_m - new_m)   # rescale new block
        l_new = l * alpha + blk_l * beta
        out_new = (out * alpha[..., None].swapaxes(1, 2)
                   + blk_out * beta[..., None].swapaxes(1, 2))
        # rotate K/V to the next device (ring over the mesh axis)
        perm = [(j, (j - 1) % n_dev) for j in range(n_dev)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return (out_new, new_m, l_new, kk, vv), None

    out0 = jnp.zeros_like(q)

    # mark the softmax stats as varying over the ring axis so the scan carry
    # types line up under shard_map's per-device type tracking
    m0 = mark_varying(jnp.full((b, h, s), -jnp.inf, q.dtype), like=q)
    l0 = mark_varying(jnp.zeros((b, h, s), q.dtype), like=q)
    (out, m, l, _, _), _ = lax.scan(step, (out0, m0, l0, k, v),
                                    jnp.arange(n_dev))
    return out / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)


# ---------------------------------------------------------------------------
# Ring FLASH attention: the ring schedule with the Pallas flash kernels as
# the per-block compute. ring_attention's _block_attn materializes the
# (S/n, S/n) score matrix per rotation in HBM; here each block runs the
# VMEM-tiled online softmax instead, so per-device memory stays O(S/n)
# even for very long local shards. Differentiation is owned by the ring:
# a custom_vjp whose backward makes the same K/V trip and calls the block
# backward kernels against the ring-MERGED (out, lse) — each block's
# recomputed p is then exactly the global probabilities restricted to the
# block, so summed dq / routed-home dk,dv are the exact global gradients.
# ---------------------------------------------------------------------------

def _block_bias(src, s_loc, valid_len):
    """(1, s_loc) f32 additive score bias for the K/V block owned by ring
    position `src`: 0 for keys inside the global valid length, -inf for
    the tail padding (which lives in the last shard)."""
    cols = src * s_loc + jnp.arange(s_loc)
    return jnp.where(cols < valid_len, 0.0, -jnp.inf).astype(
        jnp.float32)[None, :]


def _merge_blocks(O, LSE, out_b, lse_b):
    """Online-softmax merge of a new normalized block (out_b, lse_b) into
    the running (O, LSE). All f32; O (BH,S,D), LSE (BH,1,S)."""
    M = jnp.maximum(LSE, lse_b)
    a = jnp.exp(LSE - M)        # 0 at the -inf init
    bw = jnp.exp(lse_b - M)
    denom = a + bw
    row = lambda t: t[:, 0, :, None]        # (BH,1,S) -> (BH,S,1)
    O_new = (O * row(a) + out_b * row(bw)) / row(denom)
    return O_new, M + jnp.log(denom)


def _ring_rotate(axis_name, *arrays):
    n = lax.axis_size(axis_name)
    perm = [(j, (j - 1) % n) for j in range(n)]
    return tuple(lax.ppermute(a, axis_name, perm) for a in arrays)


def _block_pred(i, causal, my, src, s_loc, valid_len):
    """Whether ring step i's K/V block contributes anything, or None for
    'always'. Two skip reasons share one cond: causal blocks strictly in
    the future (i>0, src>my), and ENTIRELY-padded shards (src*s_loc >=
    valid_len). The latter is a correctness requirement, not just a
    saving: a fully-masked flash block emits lse = log(1e-30) ~ -69 (the
    l_safe clamp), and merging that phantom term would dominate whenever
    genuine scores sit below ~ -69."""
    pred = None
    if causal and i > 0:
        pred = src < my
    if valid_len is not None:
        live = src * s_loc < valid_len
        pred = live if pred is None else pred & live
    return pred


def _ring_flash_loop(q2, k2, v2, axis_name, causal, valid_len, interpret):
    from .flash_attention import flash_block

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    bh, s, d = q2.shape
    O = mark_varying(jnp.zeros((bh, s, d), jnp.float32), like=q2)
    LSE = mark_varying(jnp.full((bh, 1, s), -jnp.inf, jnp.float32), like=q2)
    kk, vv = k2, v2
    for i in range(n):  # n is static under shard_map; unrolled
        src = (my + i) % n

        def compute(O, LSE, kk, vv, src=src, i=i):
            bias = (None if valid_len is None
                    else _block_bias(src, s, valid_len))
            out_b, lse_b = flash_block(q2, kk, vv, causal=causal and i == 0,
                                       k_bias=bias, interpret=interpret)
            return _merge_blocks(O, LSE, out_b.astype(jnp.float32), lse_b)

        pred = _block_pred(i, causal, my, src, s, valid_len)
        if pred is None:
            O, LSE = compute(O, LSE, kk, vv)
        else:
            O, LSE = lax.cond(pred, compute,
                              lambda O, LSE, kk, vv: (O, LSE),
                              O, LSE, kk, vv)
        if i < n - 1:
            kk, vv = _ring_rotate(axis_name, kk, vv)
    return O, LSE


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, valid_len, interpret):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, valid_len,
                             interpret)
    return out


def _to_heads2(t):
    b, s, h, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_heads2(t2, b, h):
    bh, s, d = t2.shape
    return t2.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _ring_flash_fwd(q, k, v, axis_name, causal, valid_len, interpret):
    b, s, h, d = q.shape
    O, LSE = _ring_flash_loop(_to_heads2(q), _to_heads2(k), _to_heads2(v),
                              axis_name, causal, valid_len, interpret)
    out = _from_heads2(O.astype(q.dtype), b, h)
    return out, (q, k, v, out, LSE)


def _ring_flash_bwd(axis_name, causal, valid_len, interpret, res, dout):
    from .flash_attention import _delta, flash_block_bwd

    q, k, v, out, LSE = res
    b, s, h, d = q.shape
    q2, k2, v2 = _to_heads2(q), _to_heads2(k), _to_heads2(v)
    out2, do2 = _to_heads2(out), _to_heads2(dout)
    delta = _delta(do2, out2)   # global rowsum(dO*O), shared by blocks
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    dq = mark_varying(jnp.zeros(q2.shape, jnp.float32), like=q2)
    dkk = mark_varying(jnp.zeros(k2.shape, jnp.float32), like=q2)
    dvv = mark_varying(jnp.zeros(v2.shape, jnp.float32), like=q2)
    kk, vv = k2, v2
    for i in range(n):
        src = (my + i) % n

        def compute(dq, dkk, dvv, kk, vv, src=src, i=i):
            bias = (None if valid_len is None
                    else _block_bias(src, s, valid_len))
            dq_i, dk_b, dv_b = flash_block_bwd(
                q2, kk, vv, out2, LSE, do2, causal=causal and i == 0,
                k_bias=bias, interpret=interpret, delta=delta)
            return (dq + dq_i.astype(jnp.float32),
                    dkk + dk_b.astype(jnp.float32),
                    dvv + dv_b.astype(jnp.float32))

        pred = _block_pred(i, causal, my, src, s, valid_len)
        if pred is None:
            dq, dkk, dvv = compute(dq, dkk, dvv, kk, vv)
        else:
            dq, dkk, dvv = lax.cond(
                pred, compute,
                lambda dq, dkk, dvv, kk, vv: (dq, dkk, dvv),
                dq, dkk, dvv, kk, vv)
        # rotate the K/V blocks AND their gradient accumulators together:
        # after the full n rotations each dk/dv block is back home at the
        # device that owns that K/V shard. The final hop moves only the
        # accumulators — nobody reads kk/vv again.
        if i < n - 1:
            kk, vv, dkk, dvv = _ring_rotate(axis_name, kk, vv, dkk, dvv)
        else:
            dkk, dvv = _ring_rotate(axis_name, dkk, dvv)
    return (_from_heads2(dq.astype(q.dtype), b, h),
            _from_heads2(dkk.astype(k.dtype), b, h),
            _from_heads2(dvv.astype(v.dtype), b, h))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, *, axis_name: str, causal: bool = False,
                         valid_len: int | None = None,
                         interpret: bool | None = None) -> jnp.ndarray:
    """ring_attention with flash-kernel blocks: call inside shard_map with
    the LOCAL (B, S/n, H, D) shards. Differentiable (ring-level
    custom_vjp). The local shard length must satisfy the flash tiling
    rule (<= 128 or a multiple of 128) — sequence_parallel_attention's
    padding guarantees it for ring-size-multiple padded lengths."""
    return _ring_flash(q, k, v, axis_name, causal, valid_len, interpret)


def sequence_parallel_attention(q, k, v, mesh, *, seq_axis: str = "model",
                                causal: bool = False,
                                batch_axis: str | None = None,
                                use_flash: bool = False,
                                flash_interpret: bool | None = None):
    """Top-level entry: q,k,v (B,S,H,D) global arrays; shards S over
    `seq_axis` and runs ring attention under shard_map.

    Uneven sequence lengths are handled by padding S up to a multiple of
    the ring size and masking the padded key positions in every block;
    the pad rows are sliced off the output.

    batch_axis: optional mesh axis the batch dim is sharded over — pass
    'data' when running inside a DPxSP training step so the shard_map
    keeps the data-parallel batch split instead of all-gathering it.

    use_flash: per-block compute runs the Pallas flash kernels
    (ring_flash_attention) instead of the jnp online-softmax blocks —
    per-device memory stays O(S/n) with no (S/n)^2 score materialization.
    Padding then rounds the LOCAL shard up to the flash tile rule."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[seq_axis]
    s = q.shape[1]
    if use_flash and -(-s // n) > 128:
        # local shards > one tile must be 128-multiples (Mosaic tiling)
        pad = (-s) % (n * 128)
    else:
        pad = (-s) % n
    valid_len = s if pad else None
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)

    spec = P(batch_axis, seq_axis, None, None)
    if use_flash:
        inner = functools.partial(ring_flash_attention, axis_name=seq_axis,
                                  causal=causal, valid_len=valid_len,
                                  interpret=flash_interpret)
        # check_vma=False: pallas_call's internal slicing mixes varying
        # and unvarying operands in ways the vma checker rejects (the
        # jnp ring path below keeps full checking)
        fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    else:
        inner = functools.partial(ring_attention, axis_name=seq_axis,
                                  causal=causal, valid_len=valid_len)
        fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)
    out = fn(q, k, v)
    return out[:, :s] if pad else out
