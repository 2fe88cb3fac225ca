"""Sequence layers: Attention, Mamba2, MoE and the block-diffusion noise —
TPU-native layer types with NO
reference analogue (SURVEY §5.7: the reference is a CNN-era framework
with no attention op; §2.7: no MoE/EP). They make the framework's
long-context and expert-parallel machinery (ops/attention.py, ops/moe.py)
reachable from the prototxt surface, the same way every reference op is.

  layer { name: "attn" type: "Attention" bottom: "x" top: "y"
          attention_param { num_heads: 8 causal: true use_flash: true } }
  layer { name: "moe" type: "MoE" bottom: "x" top: "y" top: "moe_aux"
          loss_weight: 0 loss_weight: 0.01
          moe_param { num_experts: 8 hidden_dim: 2048 } }

Blob layout: (N, S, C). Attention declares fused QKV (3C, C) + output
projection (C, C) weights in Caffe's (num_output, K) convention; MoE
declares gate/w1/b1/w2/b2 expert banks — shard them over a mesh axis via
Solver(param_shardings={"moe": {"w1": ("model",), ...}}) for EP.

Attention has three forms (fused grouped heads, latent attention, and
compressed convolutional attention, which mixes its queries and keys ALONG
the sequence before the kernels: `AttentionLayer`). Inside Attention the
heads are produced by products over reshaped VIEWS of those blobs (rows
h*D..(h+1)*D of a blob are head h), (N, H, S, D) where the layer hands them
to the flash kernels on one device — the order the kernels read, so nothing
is re-laid out between a projection and a kernel — and (N, S, H, D) for the
ring paths and the jnp path. The per-head norm and the rotary turn are
lane-local (ops/attention.py `lane_partner`, `turn_lanes`): no slice into
halves, no concatenation, no float32 tensor of q's size in HBM (PERF.md
section 6, PR 34; tools/attention_glue_bytes.py counts it).

EP scope note: the dict rules shard the expert WEIGHT banks; the (E, C, *)
dispatched-activation shardings then follow from GSPMD operand propagation
through the batched expert einsums. For explicit activation constraints
(pinning the token all-to-alls) call ops.moe.moe_ffn(mesh=...,
expert_axis=...) directly.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from ..proto.config import FillerParameter
from .base import Layer, Shape, register


@register("LayerNorm")
class LayerNormLayer(Layer):
    """Per-position normalization over the trailing (channel) axis — the
    transformer companion to BatchNorm the reference never needed
    (layer_norm_param { eps scale_bias }). Stateless (no running stats),
    so it is the same pure function in TRAIN and TEST."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.config import LayerNormParameter
        p = self.lp.layer_norm_param or LayerNormParameter()
        self.p = p
        c = in_shapes[0][-1]
        if p.scale_bias:
            self.declare("scale", (c,),
                         FillerParameter(type="constant", value=1.0))
            self.declare("bias", (c,), FillerParameter(type="constant"))
        return [in_shapes[0]]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.p.eps)
        y = y.astype(x.dtype)
        if self.p.scale_bias:
            y = y * self.f(params["scale"]) + self.f(params["bias"])
        return [y], state


def rms_normalize(x, eps: float):
    """x / sqrt(mean(x^2, -1) + eps): the statistics in float32, the
    result in x's type; the caller applies the scale."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


@register("RMSNorm")
class RMSNormLayer(Layer):
    """x / sqrt(mean(x^2, -1) + eps) * scale (rms_norm_param { eps }):
    the statistics in float32, no mean subtracted, no bias. Stateless."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.config import RMSNormParameter
        self.p = self.lp.rms_norm_param or RMSNormParameter()
        self.declare("scale", (in_shapes[0][-1],),
                     FillerParameter(type="constant", value=1.0))
        return [in_shapes[0]]

    def apply(self, params, state, bottoms, *, train, rng):
        return [rms_normalize(self.f(bottoms[0]), self.p.eps)
                * self.f(params["scale"])], state


def attention_dims(p, c: int) -> tuple[int, int, int]:
    """(query heads, key/value heads, head size) of an attention_param over
    `c` channels; the zero defaults are one key/value head per query head
    and c / heads."""
    heads = max(p.num_heads, 1)
    kv = p.num_kv_heads or heads
    if p.head_dim == 0 and c % heads:
        raise ValueError(f"channels {c} not divisible by "
                         f"num_heads {p.num_heads}")
    if heads % kv:
        raise ValueError(f"num_heads {heads} not a multiple of "
                         f"num_kv_heads {kv}")
    return heads, kv, p.head_dim or c // heads


def latent_dims(p) -> tuple[int, int, int]:
    """(lanes without positions, rotary lanes, value lanes) of a head of a
    latent attention_param; refuses what that path has no meaning for."""
    nope, rot, vd = p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim
    if min(p.q_lora_rank, nope, rot, vd) < 1 or rot % 2:
        raise ValueError(
            "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
            "qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim")
    if (p.num_kv_heads or p.head_dim or p.window or p.sequence_parallel
            or p.bias_term or not p.rope_theta):
        raise ValueError(
            "latent attention has rope_theta and bias_term: false, and "
            "neither num_kv_heads, head_dim, window nor sequence_parallel")
    if p.block_diffusion or p.qk_norm:
        raise ValueError("latent attention has neither block_diffusion "
                         "nor qk_norm")
    from ..proto.netshape import yarn_problem
    problem = yarn_problem(p)
    if problem:
        raise ValueError(problem)
    return nope, rot, vd


def shift_rows(t, axis: int, by: int = 1):
    """shift(t)_i = t_{i - by} along `axis`, zeros where there is no such
    row: a pad and a slice, whose transpose is a slice and a pad."""
    if not by:
        return t
    pad = [(0, 0)] * t.ndim
    pad[axis] = (by, 0)
    return jax.lax.slice_in_dim(jnp.pad(t, pad), 0, t.shape[axis], axis=axis)


@register("Attention")
class AttentionLayer(Layer):
    """attention_param. Three forms: fused QKV heads (`_grouped_qkv`:
    grouped; windowed or under the block-diffusion mask; an RMSNorm on
    each query and key head; rotary over the whole head); with
    kv_lora_rank > 0, latent attention (`_setup_latent`, `_latent_qkv`):
    low-rank query and key/value projections, a head of unequal query/key
    and value widths, rotary over a part of it; and with `cca`, compressed
    convolutional attention (`_setup_cca`, `_cca_qkv`): queries and keys
    projected down, mixed along the sequence by two causal convolutions,
    summed with a mean of each other, normalised under a temperature,
    turned over a part of the head, value heads that read the current and
    the previous token. All produce their heads in the order `_head_major`
    says.

    Under the block-diffusion mask the fused form takes one bottom, the
    `[noisy | clean]` sequence (N, 2 L, C), or two: the query rows (N, L,
    C), the noisy half, and the key/value rows (N, 2 L, C), the whole. The
    output then has the queries' L rows, which are the one-bottom layer's
    first L rows of the same sequence: where nothing reads the clean half's
    output, nothing computes it."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.config import AttentionParameter
        p = self.lp.attention_param or AttentionParameter()
        self.p = p
        if len(in_shapes[0]) != 3:
            raise ValueError(
                f"Attention expects (N, S, C) bottom, got {in_shapes[0]}")
        n, s, c = in_shapes[0]
        from ..proto.netshape import attention_bottoms_problem
        problem = attention_bottoms_problem(p, in_shapes)
        if problem:
            raise ValueError(f"Attention: {problem}")
        if p.kv_lora_rank:
            self._setup_latent(c)
            return [in_shapes[0]]
        self.heads, self.kv_heads, self.head_dim = attention_dims(p, c)
        if p.rope_scaling_factor:
            raise ValueError("rope_scaling_factor (YaRN) is latent "
                             "attention's alone")
        if p.cca:
            self._setup_cca(c)
            return [in_shapes[0]]
        if p.window and not p.causal:
            raise ValueError("attention_param window needs causal: true")
        if p.sequence_parallel and (p.window or self.kv_heads != self.heads):
            raise ValueError("sequence_parallel attention has neither a "
                             "window nor grouped key/value heads")
        if p.rope_theta and self.head_dim % 2:
            raise ValueError(f"rotary positions over an odd head size "
                             f"{self.head_dim}")
        from ..proto.netshape import block_diffusion_problem
        problem = block_diffusion_problem(p, in_shapes[-1][1])
        if problem:
            raise ValueError(f"attention_param: {problem}")
        nq, nkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        self.nq, self.nkv = nq, nkv
        filler = p.weight_filler or FillerParameter(type="xavier")
        self.declare("qkv_weight", (nq + 2 * nkv, c), filler)
        self.declare("proj_weight", (c, nq), filler)
        if p.bias_term:
            bias = p.bias_filler or FillerParameter(type="constant")
            self.declare("qkv_bias", (nq + 2 * nkv,), bias)
            self.declare("proj_bias", (c,), bias)
        if p.qk_norm:
            one = FillerParameter(type="constant", value=1.0)
            self.declare("q_norm", (self.head_dim,), one)
            self.declare("k_norm", (self.head_dim,), one)
        return [in_shapes[0]]

    def _setup_latent(self, c: int):
        p = self.p
        self.latent = nope, rot, vd = latent_dims(p)
        self.heads = max(p.num_heads, 1)
        self.nq = self.heads * vd          # what the output product reads
        filler = p.weight_filler or FillerParameter(type="xavier")
        one = FillerParameter(type="constant", value=1.0)
        self.declare("q_a_weight", (p.q_lora_rank, c), filler)
        self.declare("q_norm", (p.q_lora_rank,), one)
        self.declare("q_b_weight", (self.heads * (nope + rot),
                                    p.q_lora_rank), filler)
        self.declare("kv_a_weight", (p.kv_lora_rank + rot, c), filler)
        self.declare("kv_norm", (p.kv_lora_rank,), one)
        self.declare("kv_b_weight", (self.heads * (nope + vd),
                                     p.kv_lora_rank), filler)
        self.declare("proj_weight", (c, self.nq), filler)

    def _setup_cca(self, c: int):
        from ..proto.netshape import cca_problem
        p = self.p
        problem = cca_problem(p)
        if problem:
            raise ValueError(f"attention_param: {problem}")
        h, g, d = self.heads, self.kv_heads, self.head_dim
        self.nq = h * d
        filler = p.weight_filler or FillerParameter(type="xavier")
        bias = p.bias_filler or FillerParameter(type="constant")
        self.declare("q_weight", (h * d, c), filler)
        self.declare("k_weight", (g * d, c), filler)
        self.declare("v1_weight", (g * d // 2, c), filler)
        self.declare("v2_weight", (g * d // 2, c), filler)
        self.declare("conv0_weight", ((h + g) * d, p.cca_time0), filler)
        self.declare("conv0_bias", ((h + g) * d,), bias)
        self.declare("conv1_weight", ((h + g) * d, d, p.cca_time1), filler)
        self.declare("conv1_bias", ((h + g) * d,), bias)
        self.declare("temp", (g,), FillerParameter(type="constant",
                                                   value=1.0))
        self.declare("proj_weight", (c, self.nq), filler)

    def _ring(self) -> bool:
        """Whether the sequence is sharded over the mesh's 'model' axis
        (prototxt-declared sequence parallelism on a mesh that has one)."""
        mp = self.mesh_plan
        return bool(self.p.sequence_parallel and mp is not None
                    and mp.mesh.shape.get("model", 1) > 1)

    def _head_major(self) -> bool:
        """Whether q, k and v go to the flash kernels on one device: they
        are then produced (B, H, S, D), the order the kernels read, and the
        output projection contracts the kernels' own result. The ring paths
        and the jnp path keep (B, S, H, D)."""
        return bool(self.p.use_flash) and not self._ring()

    def _latent_qkv(self, params, x, hm: bool = False):
        """q, k (N, S, H, nope + rot) and v (N, S, H, vd), or with `hm`
        all three (N, H, S, .): the rotary lanes last, the one rotary key
        head under every query head. Each comes from a product over a view
        of its declared blob in the order asked for; q is turned where it
        lies (tables that are cos 1, sin 0 on the lanes without
        positions)."""
        from ..ops.attention import (Yarn, lane_partner, rope_tables,
                                     turn_lanes)
        p = self.p
        nope, rot, vd = self.latent
        s, pairs = x.shape[1], bool(p.rope_interleave)
        w = lambda name: self.f(params[name])
        rms = lambda t, scale: rms_normalize(t, p.norm_eps) * scale
        heads = lambda t, wide: jnp.einsum(
            "nsr,hdr->nhsd" if hm else "nsr,hdr->nshd", t, wide)
        cos, sin = rope_tables(s, rot, p.rope_theta, pairs=pairs, lead=nope,
                               yarn=Yarn.of(p))
        turn = lambda t, lead: turn_lanes(
            t, lane_partner(t, rot, pairs), cos[:, nope - lead:],
            sin[:, nope - lead:], head_major=hm)
        q = rms(x @ w("q_a_weight").T, w("q_norm"))
        q = turn(heads(q, w("q_b_weight").reshape(
            self.heads, nope + rot, p.q_lora_rank)), nope)
        kv = x @ w("kv_a_weight").T
        k_r = kv[..., p.kv_lora_rank:]
        k_r = turn(k_r[:, None] if hm else k_r[:, :, None], 0)
        kv = rms(kv[..., :p.kv_lora_rank], w("kv_norm"))
        kv_b = w("kv_b_weight").reshape(self.heads, nope + vd,
                                        p.kv_lora_rank)
        k = heads(kv, kv_b[:, :nope])
        k = jnp.concatenate(
            [k, jnp.broadcast_to(k_r, (*k.shape[:-1], rot))], axis=-1)
        return q, k, heads(kv, kv_b[:, nope:])

    def _grouped_qkv(self, params, x, hm: bool = False, kv=None):
        """q (N, S, H, D), k and v (N, S, Hkv, D), or with `hm` (N, H, S,
        D): a product each over its rows of the fused blob viewed as
        heads, in the order asked for (apart, the per-head norm's statistic
        fuses into the product that feeds it); the norm and the rotary
        turn are lane-local, so a head goes from its product to the kernel
        in one more pass. `kv`: the rows k and v are taken from, where
        they are not x's (x then the first of them)."""
        from ..ops.attention import lane_partner, rope_tables, turn_lanes
        p = self.p
        kv = x if kv is None else kv
        s, d = kv.shape[1], self.head_dim
        weight = self.f(params["qkv_weight"])
        bias = self.f(params["qkv_bias"]) if p.bias_term else None

        def heads(rows, lo, n):
            t = jnp.einsum("nsc,hdc->nhsd" if hm else "nsc,hdc->nshd", rows,
                           weight[lo:lo + n * d].reshape(n, d, -1))
            if bias is None:
                return t
            b = bias[lo:lo + n * d].reshape(n, d)
            return t + (b[:, None] if hm else b)
        q, k, v = (heads(x, 0, self.heads), heads(kv, self.nq, self.kv_heads),
                   heads(kv, self.nq + self.nkv, self.kv_heads))
        # the two halves of a block-diffusion sequence sit at the same
        # positions
        tables = rope_tables(
            s, d, p.rope_theta, period=s // 2 if p.block_diffusion else 0
        ) if p.rope_theta else None

        def positioned(t, norm, tables):
            if p.qk_norm:
                t = rms_normalize(t, p.norm_eps) * self.f(params[norm])
            return turn_lanes(t, lane_partner(t, d), *tables,
                              head_major=hm) if tables else t
        # the queries are the first rows of the keys' sequence
        q_tables = [t[:x.shape[1]] for t in tables] \
            if tables and x.shape[1] < s else tables
        return (positioned(q, "q_norm", q_tables),
                positioned(k, "k_norm", tables), v)

    def _cca_qkv(self, params, x, hm: bool = False):
        """q (N, S, H, D), k and v (N, S, G, D), or with `hm` (N, H, S, D),
        of compressed convolutional attention. Everything is computed
        head-major, (N, heads, S, D): the products leave the MXU so, the
        shifts are pads and slices along S, the convolution a channel, the
        mean, the normalisation and the turn are lane-local, and the
        convolution a head is ONE batched product of every head's d lanes
        with its taps side by side (d -> time1 * d lanes), whose parts are
        then shifted and summed: a shift commutes with a product over the
        lanes. Scope `cca.project` holds the products from the bottom,
        `cca.mix` the rest."""
        from ..ops.attention import lane_partner, rope_tables, turn_lanes
        from ..utils.spans import CCA_MIX, CCA_PROJECT
        p = self.p
        h, g, d = self.heads, self.kv_heads, self.head_dim
        n, s, c = x.shape
        w = lambda name: self.f(params[name])
        heads = lambda rows: jnp.einsum("nsc,hdc->nhsd", x,
                                        rows.reshape(-1, d, c))
        with jax.named_scope(CCA_PROJECT):
            z = heads(jnp.concatenate([w("q_weight"), w("k_weight")]))
            v = heads(jnp.concatenate([w("v1_weight"), w("v2_weight")]))
        with jax.named_scope(CCA_MIX):
            # the second half of the value lanes read the previous token:
            # the product's rows one later
            v = jnp.concatenate(
                [v[:, :g // 2], shift_rows(v[:, g // 2:], 2)], axis=1)
            # everything lane-local stays in the compute type from fusion
            # to fusion (the statistic of the normalisation alone is
            # float32, inside its fusion): no float32 tensor of q's size
            # in HBM, forward or backward
            per_lane = lambda t: t.reshape(h + g, 1, d)
            taps0 = w("conv0_weight").reshape(h + g, 1, d, p.cca_time0)
            z1 = per_lane(w("conv0_bias")) + sum(
                taps0[..., i] * shift_rows(z, 2, p.cca_time0 - 1 - i)
                for i in range(p.cca_time0))
            # [out, in, tap] of a head: every tap's product at once, the
            # taps' d lanes side by side
            wide = jnp.einsum(
                "nhsd,hedt->nhste", z1,
                w("conv1_weight").reshape(h + g, d, d, p.cca_time1))
            z2 = per_lane(w("conv1_bias")) + sum(
                shift_rows(wide[..., i, :], 2, p.cca_time1 - 1 - i)
                for i in range(p.cca_time1))
            # the mean of each other, from the values before the
            # convolutions: query head i with the key head of its group,
            # key head j with the mean of its group's query heads
            zq = z[:, :h].reshape(n, g, h // g, s, d)
            zk = z[:, h:]
            q = z2[:, :h] + (0.5 * (zq + zk[:, :, None])).reshape(n, h, s, d)
            # the group's mean as a sum of slices: a reduction over the
            # group axis would be a pass of its own over a float32 copy
            k = z2[:, h:] + 0.5 * (
                sum(zq[:, :, i] for i in range(h // g)) * (g / h) + zk)
            rot = 2 * int(d * p.rotary_fraction / 2)
            tables = rope_tables(s, rot, p.rope_theta, tail=d - rot)

            def positioned(t, scale=None):
                # length sqrt(d): x / sqrt(mean(x^2))
                t = rms_normalize(t, p.norm_eps)
                if scale is not None:
                    t = t * scale
                return turn_lanes(t, lane_partner(t, rot, tail=d - rot),
                                  *tables, head_major=True)
            q, k = positioned(q), positioned(k, w("temp")[:, None, None])
        if hm:
            return q, k, v
        return tuple(t.swapaxes(1, 2) for t in (q, k, v))

    def apply(self, params, state, bottoms, *, train, rng):
        from ..ops.attention import attention, sequence_parallel_attention
        from ..ops.flash_attention import flash_attention_heads
        from ..utils.spans import CCA_OUT
        p = self.p
        x = self.f(bottoms[0])
        hm = self._head_major()
        if len(bottoms) > 1:
            q, k, v = self._grouped_qkv(params, x, hm, self.f(bottoms[1]))
        else:
            q, k, v = (self._latent_qkv if p.kv_lora_rank
                       else self._cca_qkv if p.cca
                       else self._grouped_qkv)(params, x, hm)
        mp = self.mesh_plan
        mask = dict(causal=bool(p.causal), window=p.window,
                    block_diffusion=p.block_diffusion)
        if p.rope_scaling_factor:
            # YaRN's factor on the scores, a constant of the kernels
            from ..ops.attention import Yarn
            mask["scale"] = Yarn.of(p).score_factor / math.sqrt(
                sum(self.latent[:2]))
        if self._ring():
            # prototxt-declared SP: the sequence dim shards over 'model'
            # and K/V ride the ICI ring (ops/attention.py ring_attention);
            # the batch dim stays on 'data' so DPxSP composes. use_flash
            # upgrades the per-block compute to the Pallas kernels
            # (ring_flash_attention) — O(S/n) memory, no (S/n)^2 scores
            out = sequence_parallel_attention(
                q, k, v, mp.mesh, seq_axis="model", causal=bool(p.causal),
                batch_axis="data" if mp.mesh.shape.get("data", 1) > 1
                else None, use_flash=bool(p.use_flash))
        elif hm:
            flash = lambda q, k, v: flash_attention_heads(q, k, v, **mask)
            # the flash kernels are Mosaic calls, which GSPMD cannot
            # partition: split the batch by hand (attention never mixes
            # samples)
            out = (flash(q, k, v) if mp is None
                   else mp.per_batch_shard(flash, q, k, v))
        else:
            out = attention(q, k, v, **mask)
        with (jax.named_scope(CCA_OUT) if p.cca
              else contextlib.nullcontext()):
            y = jnp.einsum(
                "nhsd,chd->nsc" if hm else "nshd,chd->nsc", out,
                self.f(params["proj_weight"]).reshape(x.shape[-1],
                                                      self.heads, -1))
        if p.bias_term:
            y = y + self.f(params["proj_bias"])
        return [y], state

    @property
    def kept_under_remat(self) -> tuple[str, ...]:
        """The flash kernel's output and its row statistics: what is
        computed again is what leads up to q, k and v."""
        from ..ops.flash_attention import KEPT_UNDER_REMAT
        return KEPT_UNDER_REMAT


def block_diffusion_noise(key, tokens, block_length: int, mask_id: int,
                          t_min: float, ignore_label: int):
    """One draw of block-diffusion noise (arXiv:2503.09573) over token ids
    (N, L): each block of block_length draws t ~ U(t_min, 1) and masks
    each of its tokens independently with probability t. Returns ids (N,
    2 L) = [noisy | clean], labels (N, L), weights (N, L) float32 = 1 / t
    where masked, and the count of masked positions. Two uniform draws, a
    repeat, compares and selects: no sort, no scatter."""
    n, l = tokens.shape
    blocks = -(-l // block_length)
    key_t, key_u = jax.random.split(key)
    t = jax.random.uniform(key_t, (n, blocks), jnp.float32, t_min, 1.0)
    t = jnp.repeat(t, block_length, axis=1)[:, :l]
    masked = jax.random.uniform(key_u, (n, l), jnp.float32) < t
    ids = jnp.concatenate([jnp.where(masked, mask_id, tokens), tokens], 1)
    return (ids, jnp.where(masked, tokens, ignore_label),
            jnp.where(masked, 1.0 / t, 0.0),
            jnp.sum(masked, dtype=jnp.float32))


@register("BlockDiffusionNoise")
class BlockDiffusionNoiseLayer(Layer):
    """block_diffusion_param: the data side of block-diffusion training.
    Bottom: token ids (N, L). Tops: ids (N, 2 L) = [x_t | x_0] for the
    Embed, labels (N, L) (ignore_label where not masked) and weights (N,
    L) (1 / t of the block where masked) for SoftmaxWithLoss, and
    optionally how many positions were masked. The draw comes from the
    layer's `rng`, which Net folds from the step's (fresh every TRAIN
    step, reproducible from a stated key); without one, key 0."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.block_diffusion_param
        if p is None or p.block_length < 1 or not 0.0 < p.t_min <= 1.0:
            raise ValueError("block_diffusion_param needs block_length >= "
                             "1 and 0 < t_min <= 1")
        if len(in_shapes[0]) != 2 or not 3 <= len(self.lp.top) <= 4:
            raise ValueError(
                f"BlockDiffusionNoise expects (N, L) token ids and tops "
                f"ids, labels, weights[, masked count]; got {in_shapes[0]} "
                f"and {len(self.lp.top)} tops")
        self.p = p
        n, l = in_shapes[0]
        return [(n, 2 * l), (n, l), (n, l), ()][:len(self.lp.top)]

    def apply(self, params, state, bottoms, *, train, rng):
        p = self.p
        key = jax.random.PRNGKey(0) if rng is None else rng
        tops = block_diffusion_noise(
            key, bottoms[0].astype(jnp.int32), p.block_length, p.mask_id,
            p.t_min, p.ignore_label)
        return list(tops[:len(self.lp.top)]), state


# what a Mamba2 layer's backward pass reads of its forward: the input
# product's result and the scan's output (with what ops/ssd.py's backward
# rule reads, `ssd.KEPT`: the carried states and two (S, H) vectors), kept
# under `remat: true` so that neither the input product nor the scan's
# forward pass runs again in the backward pass
MAMBA2_INPUT = "mamba2.zxbcdt"
MAMBA2_SCAN = "mamba2.scan_out"


@register("Mamba2")
class Mamba2Layer(Layer):
    """mamba2_param: a Mamba-2 mixer over (N, S, C) (proto/config.py
    Mamba2Parameter has the equations; ops/ssd.py the chunked scan). Five
    scopes: `ssm.project` the input product, `ssm.conv` the causal
    convolution a channel with its SiLU (shifted sums, `shift_rows`),
    `ssm.scan` softplus, decays and the recurrence, `ssm.gate` the gate
    and the grouped norm, `ssm.out` the output product. Under `remat` the
    input product's result, the scan's output and what its backward rule
    reads are kept (`kept_under_remat`); the convolution and the gate are
    computed again."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.netshape import mamba2_problem, mamba2_widths
        p = self.lp.mamba2_param
        if len(in_shapes[0]) != 3:
            raise ValueError(
                f"Mamba2 expects (N, S, C) bottom, got {in_shapes[0]}")
        problem = mamba2_problem(p, in_shapes[0][1])
        if problem:
            raise ValueError(f"mamba2_param: {problem}")
        self.p = p
        c = in_shapes[0][2]
        self.inner, conv, wide = mamba2_widths(p)
        filler = p.weight_filler or FillerParameter(type="xavier")
        bound = p.conv_kernel ** -0.5
        const = lambda v: FillerParameter(type="constant", value=v)
        self.declare("in_weight", (wide, c), filler)
        self.declare("conv_weight", (conv, p.conv_kernel), FillerParameter(
            type="uniform", min=-bound, max=bound))
        self.declare("conv_bias", (conv,), const(0.0))
        self.declare("dt_bias", (p.num_heads,), FillerParameter(
            type="softplus_inverse_log_uniform", min=p.dt_min, max=p.dt_max,
            value=p.dt_floor))
        self.declare("A_log", (p.num_heads,),
                     FillerParameter(type="log_arange"))
        self.declare("D", (p.num_heads,), const(1.0))
        self.declare("norm_scale", (self.inner,), const(1.0))
        self.declare("out_weight", (c, self.inner), filler)
        return [in_shapes[0]]

    @property
    def kept_under_remat(self) -> tuple[str, ...]:
        from ..ops.ssd import KEPT
        return MAMBA2_INPUT, MAMBA2_SCAN, KEPT

    def apply(self, params, state, bottoms, *, train, rng):
        from jax.ad_checkpoint import checkpoint_name

        from ..ops.ssd import ssd
        from ..utils.spans import (SSM_CONV, SSM_GATE, SSM_OUT, SSM_PROJECT,
                                   SSM_SCAN)
        p = self.p
        u = self.f(bottoms[0])
        n, s, _ = u.shape
        w = lambda name: self.f(params[name])
        inner, bc = self.inner, p.groups * p.state_size
        with jax.named_scope(SSM_PROJECT):
            zxbcdt = checkpoint_name(u @ w("in_weight").T, MAMBA2_INPUT)
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
        with jax.named_scope(SSM_CONV):
            taps = w("conv_weight")
            xbc = jax.nn.silu(w("conv_bias") + sum(
                taps[:, i] * shift_rows(xbc, 1, p.conv_kernel - 1 - i)
                for i in range(p.conv_kernel)))
            x, b, c = jnp.split(xbc, [inner, inner + bc], -1)
        with jax.named_scope(SSM_SCAN):
            # the vectors a head stay in the master type: the scan's decay
            # arithmetic is float32 whatever the compute type
            y = checkpoint_name(ssd(
                x.reshape(n, s, p.num_heads, p.head_dim), dt,
                params["A_log"], b.reshape(n, s, p.groups, p.state_size),
                c.reshape(n, s, p.groups, p.state_size), params["D"],
                params["dt_bias"], p.chunk).reshape(n, s, inner), MAMBA2_SCAN)
        with jax.named_scope(SSM_GATE):
            y = y * jax.nn.silu(z)
            y = rms_normalize(y.reshape(n, s, p.groups, -1), p.eps)
            y = y.reshape(n, s, inner) * w("norm_scale")
        with jax.named_scope(SSM_OUT):
            return [y @ w("out_weight").T], state


@register("MoE")
class MoELayer(Layer):
    """moe_param. Two formulations (ops/moe.py): the capacity one (GShard
    dispatch/combine tensors, softmax then top-k, tokens past capacity
    dropped, two biased matrices an expert; second top = the auxiliary
    load-balancing loss) and, with `dropless: true`, rows sorted by expert
    through grouped matrix products over experts of unbiased matrices,
    gated units of three or, with `gated: false`, ungated ones of two
    (second top = the rows each held expert received). Of the dropless
    path `scoring` (softmax over the top-k logits | sigmoid scores chosen
    under the `select_bias` blob, renormalised, times
    `routed_scaling_factor`), the `activation` (relu | silu | relu2),
    `gated` and `shared_experts` (a unit of the experts' form every token
    passes through) are parameters; their defaults are the layer as it was; "softmax_all"
    scores by the softmax over every expert and weighs by the chosen
    expert's own probability. A second bottom, when given, is what the
    router scores instead of the tensor the experts transform or, with
    `router: "logits"`, the router's logits themselves (a router that is
    layers of the net: no `gate` blob)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.moe_param
        if p is None or p.num_experts < 1 or p.hidden_dim < 1:
            raise ValueError("moe_param needs num_experts and hidden_dim")
        self.p = p
        c = in_shapes[0][-1]
        self.c = c
        held = p.experts_held or p.num_experts
        if not 0 <= p.first_expert <= p.num_experts - held:
            raise ValueError(
                f"experts {p.first_expert}..{p.first_expert + held - 1} "
                f"are not among num_experts {p.num_experts}")
        if not p.dropless and (held != p.num_experts or len(in_shapes) > 1):
            raise ValueError("moe_param: experts_held and a router bottom "
                             "need dropless: true")
        from ..proto.netshape import moe_router_problem
        problem = moe_router_problem(p, in_shapes)
        if problem:
            raise ValueError(f"moe_param: {problem}")
        from ..proto.netshape import moe_form_problem
        problem = moe_form_problem(p)
        if problem:
            raise ValueError(f"moe_param: {problem}")
        filler = p.weight_filler or FillerParameter(type="xavier")
        gate_filler = p.gate_filler or FillerParameter(type="gaussian",
                                                       std=0.02)
        zero = FillerParameter(type="constant")
        if p.router != "logits":
            self.declare("gate", (c, p.num_experts), gate_filler)
        if p.scoring != "softmax":
            self.declare("select_bias", (p.num_experts,),
                         p.bias_filler or zero)
        self.declare("w1", (held, c, p.hidden_dim), filler)
        if not p.dropless:
            self.declare("b1", (held, p.hidden_dim), zero)
        self.declare("w2", (held, p.hidden_dim, c), filler)
        if not p.dropless:
            self.declare("b2", (held, c), zero)
        elif p.gated:
            self.declare("w3", (held, c, p.hidden_dim), filler)
        if p.shared_experts:
            wide = p.shared_experts * p.hidden_dim
            self.declare("shared_w1", (c, wide), filler)
            if p.gated:
                self.declare("shared_w3", (c, wide), filler)
            self.declare("shared_w2", (wide, c), filler)
        tops = [in_shapes[0]]
        if len(self.lp.top) > 1:  # aux loss / rows per held expert
            tops.append((held,) if p.dropless else ())
        return tops

    def apply(self, params, state, bottoms, *, train, rng):
        from ..ops.moe import moe_dropless, moe_ffn
        p = self.p
        x = self.f(bottoms[0])
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        cast = {k: self.f(v) for k, v in params.items()}
        if p.dropless:
            scored = self.f(bottoms[1]).reshape(flat.shape[0], -1) \
                if len(bottoms) > 1 else flat
            y, extra = moe_dropless(
                cast, flat, scored, top_k=max(p.top_k, 1),
                first_expert=p.first_expert, scoring=p.scoring,
                scale=p.routed_scaling_factor, activation=p.activation,
                row_bound=p.row_bound)
        else:
            y, extra = moe_ffn(cast, flat, top_k=max(p.top_k, 1),
                               capacity_factor=p.capacity_factor)
        tops = [y.reshape(*lead, x.shape[-1])]
        if len(self.lp.top) > 1:
            tops.append(extra)
        return tops, state
