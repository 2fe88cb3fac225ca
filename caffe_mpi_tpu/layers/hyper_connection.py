"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606): a residual of n streams in place of
the reference's one, whose residual is an `Eltwise` SUM
(src/caffe/layers/eltwise_layer.cpp:45-80 Forward_cpu, the SUM branch).

The stream blob is (N, S, n, C): n copies of the channels a position. A
sub-layer F (attention or a feed-forward, with its pre-norm) is wrapped by
a pair of layers, in the prototxt:

  layer { name: "b/hc1" type: "HyperConnectionPre" bottom: "x"
          top: "b/hc1_in" top: "b/hc1_coef" hyper_connection_param { } }
  ... F reads b/hc1_in (N, S, C), writes b/attn ...
  layer { name: "b/hc1_out" type: "HyperConnectionPost" bottom: "x"
          bottom: "b/attn" bottom: "b/hc1_coef" top: "b/res1"
          hyper_connection_param { } }

`HyperConnectionPre` computes a token's coefficients from x' =
RMSNorm(vec(x)) over the n C lanes (no scale: one would fold into phi):
H_pre = sigmoid(a_pre x' phi_pre + b_pre), H_post = 2 sigmoid(a_post x'
phi_post + b_post), H_res = Sinkhorn-Knopp(exp(clip(a_res mat(x' phi_res) +
b_res))), and writes H_pre x (the compute type) and [H_post | H_res]
(float32, (N, S, n + n^2), H_res row-major). `HyperConnectionPost` writes
H_res x + H_post^T F. The coefficients and the Sinkhorn iterations are
float32, the stream the compute type.

TPU-native choices: x' phi is r x phi, r the norm's per-token factor, so
the product reads the stream itself (nothing of n C lanes in float32 is
written); the coefficients are computed with the tokens on the LAST axis,
(k, tokens), so that the Sinkhorn iterations' 4 x 4 matrices are sums over
leading axes of lane-dense arrays and not padded (4, 4) tiles; the mixes
are elementwise multiply-adds over the n streams, which fuse with their
reads. Both layers' backward passes are jax's own. Under `remat: true` a
`HyperConnectionPre` keeps nothing (its input is the stream, kept anyway)
and computes its coefficients again in the backward pass. Scope
`mhc.sinkhorn` holds the iterations (utils/spans.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..proto.config import FillerParameter, HyperConnectionParameter
from .base import Layer, Shape, register


def sinkhorn(m, iters: int, eps: float, axes=(-2, -1)):
    """`iters` times: rows of m divided by their sums + eps, then columns.
    `axes` are (row index, column index) of the matrices in m; a row sums
    over the column index. A loop and not unrolled: unrolled, the 20
    iterations' small fusions, forward, computed again and backward in ten
    sub-layers, made most of the compiled code of every program that holds
    the net (the train step 317 MB against 214 MB, compiled for a v5e)."""
    rows, cols = axes

    def one(_, m):
        m = m / (jnp.sum(m, axis=cols, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=rows, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, one, m)


def _param(lp) -> HyperConnectionParameter:
    from ..proto.netshape import hyper_connection_problem
    p = lp.hyper_connection_param or HyperConnectionParameter()
    problem = hyper_connection_problem(p)
    if problem:
        raise ValueError(f"hyper_connection_param: {problem}")
    return p


@register("HyperConnectionPre")
class HyperConnectionPreLayer(Layer):
    """Bottom: the stream (N, S, n, C). Tops: H_pre x (N, S, C) and the
    coefficients [H_post | H_res] (N, S, n + n^2) in float32. Blobs phi
    (2 n + n^2, n C), bias (2 n + n^2), alpha (3)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.p = _param(self.lp)
        if len(in_shapes[0]) != 4 or in_shapes[0][2] != p.streams:
            raise ValueError(f"HyperConnectionPre expects a stream (N, S, "
                             f"{p.streams}, C), got {in_shapes[0]}")
        n, s, k, c = in_shapes[0]
        out = 2 * k + k * k        # phi's outputs: H_pre, H_post, H_res
        self.declare("phi", (out, k * c), p.phi_filler or FillerParameter(
            type="gaussian", std=(k * c) ** -0.5))
        self.declare("bias", (out,), p.bias_filler
                     or FillerParameter(type="constant"))
        self.declare("alpha", (3,), p.alpha_filler
                     or FillerParameter(type="constant", value=1.0))
        return [(n, s, c), (n, s, k + k * k)]

    def apply(self, params, state, bottoms, *, train, rng):
        from ..utils.spans import MHC_SINKHORN
        p = self.p
        x = self.f(bottoms[0])
        n, s, k, c = x.shape
        flat = x.reshape(n * s, k * c)
        # the norm's factor a token; x' phi = r (x phi)
        sq = jnp.mean(jnp.square(flat.astype(jnp.float32)), axis=-1)
        r = jax.lax.rsqrt(sq + p.eps)
        phi, bias, alpha = (params[b].astype(jnp.float32)
                            for b in ("phi", "bias", "alpha"))
        # (2 n + n^2, tokens): the tokens on the lanes
        logits = jnp.einsum("kc,tc->kt", phi.astype(x.dtype), flat,
                            preferred_element_type=jnp.float32) * r
        scale = jnp.repeat(alpha, jnp.array((k, k, k * k)),
                           total_repeat_length=phi.shape[0])
        z = scale[:, None] * logits + bias[:, None]
        h_pre = jax.nn.sigmoid(z[:k])
        h_post = 2.0 * jax.nn.sigmoid(z[k:2 * k])
        m = jnp.exp(jnp.clip(z[2 * k:], -p.clamp, p.clamp))
        with jax.named_scope(MHC_SINKHORN):
            # [row i, column j, token]
            h_res = sinkhorn(m.reshape(k, k, n * s), p.sinkhorn_iters,
                             p.sinkhorn_eps, axes=(0, 1))
        x32 = flat.reshape(n * s, k, c).astype(jnp.float32)
        mixed = jnp.sum(h_pre.T[:, :, None] * x32, axis=1)
        coef = jnp.concatenate([h_post, h_res.reshape(k * k, n * s)])
        return [mixed.astype(x.dtype).reshape(n, s, c),
                coef.T.reshape(n, s, -1)], state


@register("HyperConnectionPost")
class HyperConnectionPostLayer(Layer):
    """Bottoms: the stream x (N, S, n, C), the sub-layer's output y (N, S,
    C) and the coefficients of its `HyperConnectionPre`. Top: the stream
    H_res x + H_post^T y, stream i = sum_j H_res[i, j] x_j + H_post[i] y,
    in float32 and written in the compute type. No blobs."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.p = _param(self.lp)
        if len(in_shapes) != 3:
            raise ValueError("HyperConnectionPost has three bottoms: the "
                             "stream, the sub-layer's output and the "
                             "coefficients")
        stream, out, coef = in_shapes
        n, s, k, c = stream if len(stream) == 4 else (None,) * 4
        if (k != p.streams or tuple(out) != (n, s, c)
                or tuple(coef) != (n, s, k + k * k)):
            raise ValueError(f"HyperConnectionPost expects a stream (N, S, "
                             f"{p.streams}, C), (N, S, C) and (N, S, "
                             f"{p.streams * (p.streams + 1)}); got "
                             f"{in_shapes}")
        return [tuple(stream)]

    def apply(self, params, state, bottoms, *, train, rng):
        x, y = self.f(bottoms[0]), self.f(bottoms[1])
        coef = bottoms[2].astype(jnp.float32)
        n, s, k, c = x.shape
        h_post = coef[..., :k]
        h_res = coef[..., k:].reshape(n, s, k, k)
        out = jnp.sum(h_res[..., None] * x.astype(jnp.float32)[:, :, None],
                      axis=3) \
            + h_post[..., None] * y.astype(jnp.float32)[:, :, None]
        return [out.astype(x.dtype)], state
