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

TPU-native choices: both layers see the stream as one flat view (N S,
n C), stream j the lane slice [:, j C:(j + 1) C] (lane-aligned where C is
a multiple of 128); no operation puts the n streams on an axis of their
own, where they would sit on the second-minor axis and XLA:TPU would move
the stream between two layouts and write float32 copies of it. x' phi is
r x phi, r the norm's per-token factor, so the product reads the stream
itself; the coefficients are computed with the tokens on the LAST axis,
(k, tokens), so that the Sinkhorn iterations' 4 x 4 matrices are sums
over leading axes of lane-dense arrays and not padded (4, 4) tiles. Each
layer's forward and backward passes are written by hand (`jax.custom_vjp`)
over that view, and every pass reads the stream in the compute type and
casts inside its fusion: nothing of the stream's size is written in
float32. `HyperConnectionPost`'s backward pass reads its cotangent, x and
F (dx, dF, and dH_res, dH_post as per-token row sums); it keeps only its
inputs, so under `remat: true` its forward is not computed again.
`HyperConnectionPre`'s backward pass reads the kept product and norm
factor, computes dH_pre as row sums, dphi as one product with x, and dx as
one product whose epilogue adds H_pre dmixed (written in the compute type)
and the norm's term; the coefficients' own math (2 n + n^2 rows of floats
a token: sigmoid, clip, exp, Sinkhorn) keeps jax's VJP. Under `remat:
true` a `HyperConnectionPre` keeps the product and the norm factor
(`kept_under_remat`, 2 n + n^2 + 1 floats a token) beside its input, and
computes the coefficients again from them. Both passes are jitted
functions: one trace serves every layer of a recipe at the same shapes,
which keeps the set-up's tracing below what jax's own backward passes
cost. Scope `mhc.sinkhorn` holds the iterations (utils/spans.py), forward
and backward; the hand-written passes run inside the layers' own scopes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


def _streams(flat, n: int) -> list:
    """The n lane slices of the flat view (tokens, n C), each (tokens, C),
    in float32 (a cast the reading fusion does)."""
    return [s.astype(jnp.float32) for s in jnp.split(flat, n, axis=1)]


def _cols(a) -> list:
    """The columns of a (tokens, m) array, each (tokens, 1): one number a
    token that multiplies every lane of a stream."""
    return jnp.split(a, a.shape[1], axis=1)


# -- HyperConnectionPre ------------------------------------------------------

def _logits(x, phi, eps: float):
    """(x phi)^T as (2 n + n^2, tokens) in float32 and the norm's factor r
    a token: x' phi = r (x phi)."""
    sq = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1)
    r = jax.lax.rsqrt(sq + eps)
    lg = jax.lax.dot_general(phi, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return lg, r


def _coefficients(u, bias, alpha, cfg):
    """H_pre (n, tokens) and [H_post | H_res] (tokens, n + n^2) from the
    normed logits u = r x phi, (2 n + n^2, tokens), all float32."""
    from ..utils.spans import MHC_SINKHORN
    k, _, clamp, iters, sk_eps = cfg
    t = u.shape[1]
    scale = jnp.repeat(alpha, jnp.array((k, k, k * k)),
                       total_repeat_length=u.shape[0])
    z = scale[:, None] * u + bias[:, None]
    h_pre = jax.nn.sigmoid(z[:k])
    h_post = 2.0 * jax.nn.sigmoid(z[k:2 * k])
    m = jnp.exp(jnp.clip(z[2 * k:], -clamp, clamp))
    with jax.named_scope(MHC_SINKHORN):
        # [row i, column j, token]
        h_res = sinkhorn(m.reshape(k, k, t), iters, sk_eps, axes=(0, 1))
    return h_pre, jnp.concatenate([h_post, h_res.reshape(k * k, t)]).T


def _mix(h_pre, x):
    """H_pre x, (tokens, C) in x's type."""
    return sum(h[:, None] * xj for h, xj in zip(h_pre, _streams(
        x, h_pre.shape[0]))).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(x, phi, bias, alpha, cfg):
    """(H_pre x (tokens, C), [H_post | H_res] (tokens, n + n^2)) of the
    flat stream x (tokens, n C); phi (2 n + n^2, n C) in x's type, bias
    and alpha float32; cfg (n, eps, clamp, Sinkhorn iterations, its
    eps)."""
    lg, r = _logits(x, phi, cfg[1])
    h_pre, coef = _coefficients(lg * r, bias, alpha, cfg)
    return _mix(h_pre, x), coef


# Under a `HyperConnectionPre`'s `remat: true` the product and the norm's
# factor are kept (`HyperConnectionPreLayer.kept_under_remat`), 2 n + n^2 + 1
# floats a token, so the backward pass does not read the stream for them.
KEPT_UNDER_REMAT = ("hc_pre.logits", "hc_pre.norm")


def _pre_fwd(x, phi, bias, alpha, cfg):
    lg, r = (checkpoint_name(a, name) for a, name in
             zip(_logits(x, phi, cfg[1]), KEPT_UNDER_REMAT))
    (h_pre, coef), small = jax.vjp(
        lambda u, b, a: _coefficients(u, b, a, cfg), lg * r, bias, alpha)
    return (_mix(h_pre, x), coef), (x, phi, lg, r, h_pre, small)


def _pre_bwd(cfg, res, cts):
    x, phi, lg, r, h_pre, small = res
    dmixed, dcoef = cts
    dm = dmixed.astype(jnp.float32)
    dh_pre = jnp.stack([jnp.sum(dm * xj, axis=1)
                        for xj in _streams(x, cfg[0])])
    du, dbias, dalpha = small((dh_pre, dcoef))
    dlg = du * r
    dr = jnp.sum(du * lg, axis=0)
    dphi = jax.lax.dot_general(dlg, x, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # dx: the product with phi, whose epilogue adds H_pre's mix and the
    # norm's factor's term (dr/dx = -r^3 x / (n C))
    norm = (-dr * r ** 3 / x.shape[1])[:, None]
    dx = (jax.lax.dot_general(dlg, phi, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + norm * x.astype(jnp.float32)
          + jnp.concatenate([(h[:, None] * dm).astype(x.dtype)
                             for h in h_pre], 1))
    return dx.astype(x.dtype), dphi.astype(phi.dtype), dbias, dalpha


_pre.defvjp(_pre_fwd, _pre_bwd)
# one trace serves every layer of the same shapes (a recipe's ten
# hyper-connections differ only in their arguments)
_pre = jax.jit(_pre, static_argnums=(4,))


# -- HyperConnectionPost -----------------------------------------------------

def _post_mix(x, y, coef, k):
    """Stream i = sum_j H_res[i, j] x_j + H_post[i] y over the flat view:
    x (tokens, n C), y (tokens, C), coef [H_post | H_res] (tokens, n +
    n^2) float32, H_res row-major. In float32, written in x's type."""
    xs, y32, h = _streams(x, k), y.astype(jnp.float32), _cols(coef)
    return jnp.concatenate([
        sum(h[k + i * k + j] * xs[j] for j in range(k)) + h[i] * y32
        for i in range(k)], axis=1).astype(x.dtype)


_post = jax.custom_vjp(_post_mix, nondiff_argnums=(3,))


def _post_fwd(x, y, coef, k):
    """Its residuals are its inputs: under `remat: true` nothing of the
    forward pass is computed again."""
    return _post_mix(x, y, coef, k), (x, y, coef)


def _post_bwd(k, res, dout):
    x, y, coef = res
    gs, xs, y32 = _streams(dout, k), _streams(x, k), y.astype(jnp.float32)
    h = _cols(coef)
    dx = jnp.concatenate([sum(h[k + i * k + j] * gs[i] for i in range(k))
                          for j in range(k)], axis=1)
    dy = sum(h[i] * g for i, g in enumerate(gs))
    dcoef = jnp.stack(
        [jnp.sum(g * y32, axis=1) for g in gs]
        + [jnp.sum(gs[i] * xs[j], axis=1) for i in range(k)
           for j in range(k)], axis=1)
    return dx.astype(x.dtype), dy.astype(y.dtype), dcoef


_post.defvjp(_post_fwd, _post_bwd)
_post = jax.jit(_post, static_argnums=(3,))


@register("HyperConnectionPre")
class HyperConnectionPreLayer(Layer):
    """Bottom: the stream (N, S, n, C). Tops: H_pre x (N, S, C) and the
    coefficients [H_post | H_res] (N, S, n + n^2) in float32. Blobs phi
    (2 n + n^2, n C), bias (2 n + n^2), alpha (3)."""

    kept_under_remat = KEPT_UNDER_REMAT

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
        p = self.p
        x = self.f(bottoms[0])
        n, s, k, c = x.shape
        phi = params["phi"].astype(x.dtype)
        bias, alpha = (params[b].astype(jnp.float32)
                       for b in ("bias", "alpha"))
        mixed, coef = _pre(x.reshape(n * s, k * c), phi, bias, alpha,
                           (k, p.eps, p.clamp, p.sinkhorn_iters,
                            p.sinkhorn_eps))
        return [mixed.reshape(n, s, c), coef.reshape(n, s, -1)], state


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
        out = _post(x.reshape(n * s, k * c), y.reshape(n * s, c),
                    coef.reshape(n * s, -1), k)
        return [out.reshape(n, s, k, c)], state
