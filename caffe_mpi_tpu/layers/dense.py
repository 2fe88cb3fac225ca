"""Dense layers: InnerProduct, Embed, Bias, Scale.

Reference: src/caffe/layers/{inner_product,embed,bias,scale}_layer.{cpp,cu}.
InnerProduct's cuBLAS gemm calls become a single jnp.dot lowered onto the
MXU; Bias/Scale broadcast arithmetic is fused by XLA into neighboring ops.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..proto.config import FillerParameter
from .base import Layer, Shape, register


@register("InnerProduct")
class InnerProductLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.inner_product_param
        self.p = p
        self.axis = p.axis % len(in_shapes[0]) if p.axis < 0 else p.axis
        k = math.prod(in_shapes[0][self.axis:])
        self.k = k
        # Caffe stores (num_output, K), or (K, num_output) when transpose
        wshape = (k, p.num_output) if p.transpose else (p.num_output, k)
        self.declare("weight", wshape, p.weight_filler)
        if p.bias_term:
            self.declare("bias", (p.num_output,),
                         p.bias_filler or FillerParameter(type="constant"))
        return [(*in_shapes[0][: self.axis], p.num_output)]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        lead = x.shape[: self.axis]
        x2 = x.reshape(math.prod(lead) if lead else 1, self.k)
        w = self.f(params["weight"])
        y = jnp.matmul(x2, w if self.p.transpose else w.T,
                       precision=self.policy.lax_precision)
        if self.p.bias_term:
            y = y + self.f(params["bias"])
        return [y.reshape(*lead, self.p.num_output)], state


# `_lookup`'s backward pass sums equal ids' rows by a matrix product whose
# cost is known, 2 T^2 F FLOPs for T tokens of F features (x 4 MXU passes
# in float32, measured), where the cost of XLA:TPU's row scatter-add is not:
# on the v5e it took 0.4-3.4 ms at most shapes and 15-17 ms from 6,144 to
# 32,768 rows of 2,560 bf16 features into 37,984 (PERF.md section 6, PR 28).
# The product is taken while the chip's peak (197e12 FLOP/s) would run it in
# under half of those 15 ms, so that neither choice can lose more than about
# 7 ms; past that `jnp.take`'s own transpose rule stands. The language-model
# cell is at 1.7e11 (2.8 against 15.1 ms).
_LOOKUP_PRODUCT_LIMIT = 0.5 * 15e-3 * 197e12 / 2     # T^2 F passes: 7.4e11


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(table, ids, rows):
    """table[ids] for flat ids into a (rows, F) table."""
    return jnp.take(table, ids, axis=0)


def _lookup_bwd(rows, ids, g):
    """The table's gradient without a row scatter. Every token's row becomes
    the float32 sum over all tokens of its id (a 0/1 compare matrix times
    the cotangent, on the MXU), rounded once; a scalar scatter notes one
    position for each id present, and the table's rows are gathered from
    there, absent ids reading zero."""
    count, width = g.shape
    f32 = g.dtype == jnp.float32
    if count * count * width * (4 if f32 else 1) > _LOOKUP_PRODUCT_LIMIT:
        return jnp.zeros((rows, width), g.dtype).at[ids].add(g), None
    same = (ids[:, None] == ids[None, :]).astype(g.dtype)
    tot = jnp.dot(same, g, preferred_element_type=jnp.float32,
                  precision="highest" if f32 else None).astype(g.dtype)
    at = jnp.full((rows,), count, jnp.int32).at[ids].set(
        jnp.arange(count, dtype=jnp.int32), mode="drop")
    return jnp.take(tot, at, axis=0, mode="fill", fill_value=0), None


_lookup.defvjp(lambda table, ids, rows: (_lookup(table, ids, rows), ids),
               _lookup_bwd)


@register("Embed")
class EmbedLayer(Layer):
    """Index lookup: a one-hot matmul in the reference (embed_layer.cu),
    here a row gather whose backward pass is `_lookup_bwd`."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.embed_param
        self.p = p
        self.declare("weight", (p.input_dim, p.num_output), p.weight_filler)
        if p.bias_term:
            self.declare("bias", (p.num_output,),
                         p.bias_filler or FillerParameter(type="constant"))
        return [(*in_shapes[0], p.num_output)]

    def apply(self, params, state, bottoms, *, train, rng):
        idx = bottoms[0].astype(jnp.int32)
        y = _lookup(self.f(params["weight"]), idx.reshape(-1),
                    self.p.input_dim).reshape(*idx.shape, -1)
        if self.p.bias_term:
            y = y + self.f(params["bias"])
        return [y], state


def _broadcast_along(vec: jnp.ndarray, nd: int, axis: int) -> jnp.ndarray:
    """Reshape a (num_axes...)-shaped param so it broadcasts against an
    nd-dim input starting at `axis` (scale_layer.cpp multicast logic)."""
    shape = [1] * nd
    for i, s in enumerate(vec.shape):
        shape[axis + i] = s
    return vec.reshape(shape)


class _ScaleBiasBase(Layer):
    """Shared logic: param shape = bottom shape[axis : axis+num_axes], or the
    second bottom provides the operand."""

    def _setup(self, in_shapes, axis: int, num_axes: int, filler, default_fill):
        self.two_bottom = len(in_shapes) > 1
        nd = len(in_shapes[0])
        self.axis = axis % nd if axis < 0 else axis
        if self.two_bottom:
            self.op_shape = in_shapes[1]
        else:
            if num_axes == -1:
                self.op_shape = in_shapes[0][self.axis:]
            else:
                self.op_shape = in_shapes[0][self.axis : self.axis + num_axes]
            self.declare("operand", tuple(self.op_shape),
                         filler or FillerParameter(type="constant", value=default_fill))
        return [in_shapes[0]]

    def _operand(self, params, bottoms, nd):
        if self.two_bottom:
            return _broadcast_along(self.f(bottoms[1]), nd, self.axis)
        return _broadcast_along(self.f(params["operand"]), nd, self.axis)


@register("Scale")
class ScaleLayer(_ScaleBiasBase):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.scale_param
        self.p = p
        out = self._setup(in_shapes, p.axis if p else 1,
                          p.num_axes if p else 1,
                          p.filler if p else None, default_fill=1.0)
        self.bias_term = bool(p and p.bias_term)
        if self.bias_term:
            self.declare("bias", tuple(self.op_shape),
                         (p.bias_filler if p else None)
                         or FillerParameter(type="constant"))
        return out

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        y = x * self._operand(params, bottoms, x.ndim)
        if self.bias_term:
            y = y + _broadcast_along(self.f(params["bias"]), x.ndim, self.axis)
        return [y], state


@register("Bias")
class BiasLayer(_ScaleBiasBase):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.bias_param
        return self._setup(in_shapes, p.axis if p else 1,
                           p.num_axes if p else 1,
                           p.filler if p else None, default_fill=0.0)

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        return [x + self._operand(params, bottoms, x.ndim)], state
