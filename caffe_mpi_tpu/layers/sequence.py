"""Sequence layers: Attention + MoE — TPU-native layer types with NO
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

EP scope note: the dict rules shard the expert WEIGHT banks; the (E, C, *)
dispatched-activation shardings then follow from GSPMD operand propagation
through the batched expert einsums. For explicit activation constraints
(pinning the token all-to-alls) call ops.moe.moe_ffn(mesh=...,
expert_axis=...) directly.
"""

from __future__ import annotations

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


@register("Attention")
class AttentionLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.config import AttentionParameter
        p = self.lp.attention_param or AttentionParameter()
        self.p = p
        if len(in_shapes[0]) != 3:
            raise ValueError(
                f"Attention expects (N, S, C) bottom, got {in_shapes[0]}")
        n, s, c = in_shapes[0]
        if c % max(p.num_heads, 1):
            raise ValueError(f"channels {c} not divisible by "
                             f"num_heads {p.num_heads}")
        self.heads = max(p.num_heads, 1)
        filler = p.weight_filler or FillerParameter(type="xavier")
        self.declare("qkv_weight", (3 * c, c), filler)
        self.declare("proj_weight", (c, c), filler)
        if p.bias_term:
            bias = p.bias_filler or FillerParameter(type="constant")
            self.declare("qkv_bias", (3 * c,), bias)
            self.declare("proj_bias", (c,), bias)
        return [in_shapes[0]]

    def apply(self, params, state, bottoms, *, train, rng):
        from ..ops.attention import attention, sequence_parallel_attention
        p = self.p
        x = self.f(bottoms[0])
        n, s, c = x.shape
        qkv = x @ self.f(params["qkv_weight"]).T
        if p.bias_term:
            qkv = qkv + self.f(params["qkv_bias"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (n, s, self.heads, c // self.heads)
        q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
        mp = self.mesh_plan
        if (p.sequence_parallel and mp is not None
                and mp.mesh.shape.get("model", 1) > 1):
            # prototxt-declared SP: the sequence dim shards over 'model'
            # and K/V ride the ICI ring (ops/attention.py ring_attention);
            # the batch dim stays on 'data' so DPxSP composes. use_flash
            # upgrades the per-block compute to the Pallas kernels
            # (ring_flash_attention) — O(S/n) memory, no (S/n)^2 scores
            out = sequence_parallel_attention(
                q, k, v, mp.mesh, seq_axis="model", causal=bool(p.causal),
                batch_axis="data" if mp.mesh.shape.get("data", 1) > 1
                else None, use_flash=bool(p.use_flash))
        elif p.use_flash and mp is not None:
            # the flash kernels are Mosaic calls, which GSPMD cannot
            # partition: split the batch by hand (attention never mixes
            # samples)
            out = mp.per_batch_shard(
                lambda q, k, v: attention(q, k, v, causal=bool(p.causal),
                                          use_flash=True), q, k, v)
        else:
            out = attention(q, k, v, causal=bool(p.causal),
                            use_flash=bool(p.use_flash))
        y = out.reshape(n, s, c) @ self.f(params["proj_weight"]).T
        if p.bias_term:
            y = y + self.f(params["proj_bias"])
        return [y], state


@register("MoE")
class MoELayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.moe_param
        if p is None or p.num_experts < 1 or p.hidden_dim < 1:
            raise ValueError("moe_param needs num_experts and hidden_dim")
        self.p = p
        c = in_shapes[0][-1]
        self.c = c
        filler = p.weight_filler or FillerParameter(type="xavier")
        gate_filler = FillerParameter(type="gaussian", std=0.02)
        self.declare("gate", (c, p.num_experts), gate_filler)
        self.declare("w1", (p.num_experts, c, p.hidden_dim), filler)
        self.declare("b1", (p.num_experts, p.hidden_dim),
                     FillerParameter(type="constant"))
        self.declare("w2", (p.num_experts, p.hidden_dim, c), filler)
        self.declare("b2", (p.num_experts, c),
                     FillerParameter(type="constant"))
        tops = [in_shapes[0]]
        if len(self.lp.top) > 1:  # optional aux-loss top
            tops.append(())
        return tops

    def apply(self, params, state, bottoms, *, train, rng):
        from ..ops.moe import moe_ffn
        p = self.p
        x = self.f(bottoms[0])
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        y, aux = moe_ffn({k: self.f(v) for k, v in params.items()}, flat,
                         top_k=max(p.top_k, 1),
                         capacity_factor=p.capacity_factor)
        tops = [y.reshape(*lead, x.shape[-1])]
        if len(self.lp.top) > 1:
            tops.append(aux)
        return tops, state
