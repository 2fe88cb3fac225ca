"""Across-channels LRN in plain jnp/lax: the path every operand takes
that does not go to the Pallas kernels of `ops/lrn.py` (float32 always,
any dtype under `CAFFE_LRN_PALLAS=0`). This module imports no Pallas:
`jax.experimental.pallas` costs 1.2 s at its first import, and an f32
net never needs it (PERF.md section 5).

    y_i  = x_i * s_i^-beta,  s_i = k + (alpha/n) * sum_{W(i)} x_j^2
    dx_m = dy_m * s_m^-beta
           - (2*alpha*beta/n) * x_m * sum_{W(m)} dy_i x_i s_i^{-beta-1}

(lrn_layer.cpp:94-116 forward, the lrn_layer.cu backward identity: the
same two lines `ops/lrn.py` computes in VMEM.) The backward is written
out, not left to reverse-mode AD: AD keeps `s` and `s^-beta` as two more
activation-sized residuals and takes a second `power`; here the one
residual is `x`, the scale is recomputed from it, and the one
`exp(-beta*log(s))` serves both terms (`s^{-beta-1}` is a divide).

`W`, the centred window over C truncated at the edges, is a product
with the C x C 0/1 band matrix. XLA:TPU holds a convolution's
activations with C on the sublanes; as `size` shifted slices of a
padded copy the sum was `size` sublane-misaligned reads and a `kLoop`
fusion bound by vector work at a fifth of HBM speed (AlexNet f32 at
batch 1024, PR 38: 56 of the step's 110 ms). As a product it is the
MXU's, and the squares, the power and the final multiply fuse around it
(PR 34 found the same rule on the lanes: XLA:TPU fuses a permutation
with what reads it only when it is a product). `Precision.HIGHEST`
keeps a float32 operand's accuracy: the band is exact in bf16, the
squares are not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def window_sum(t: jax.Array, size: int) -> jax.Array:
    """out[:, i] = sum of t[:, j] over |i - j| <= (size-1)/2, zero beyond
    the edges; `t` is (N, C, spatial...). The band rides as the kernel of
    a 1x1 convolution. Two other spellings of the same sum are refused
    by XLA:TPU (libtpu 0.0.34), both found by deviceless compiles
    (tests/test_tpu_aot_compile.py keeps them):

    - `einsum('nchw,cd->ndhw')`: behind conv1's weight gradient its
      dot_general compiles for 94-213 s at batches 3-20 that are no
      multiple of 8 (PR 39; this spelling 2-6 s at every batch tried, 1
      to 1024);
    - a padded `lax.reduce_window` over the channel axis: the AlexNet
      deploy net at batch 1 and 4 fails with "INVALID_ARGUMENT: during
      context [post-optimization]: Binary op with incompatible shapes:
      f32[55,8,8,96] and f32[55,8,8,92]": the window's padding is lost
      somewhere after it fuses behind conv1 (batch 10 and 256 compile;
      an explicit jnp.pad + VALID window is folded back and fails
      alike)."""
    c = np.arange(t.shape[1])
    band = np.abs(c[:, None] - c[None, :]) <= (size - 1) // 2
    spatial = t.ndim - 2
    kernel = jnp.asarray(band, t.dtype).reshape(band.shape + (1,) * spatial)
    return lax.conv_general_dilated(t, kernel, (1,) * spatial, "VALID",
                                    precision=lax.Precision.HIGHEST)


def _scale_pow(x, size, alpha, beta, k):
    """(s, s^-beta) in x's dtype."""
    scale = k + (alpha / size) * window_sum(x * x, size)
    return scale, jnp.exp(-beta * jnp.log(scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn_across_channels(x: jax.Array, size: int, alpha: float, beta: float,
                        k: float) -> jax.Array:
    """Across-channels LRN of an (N, C, ...) operand, computed in its
    dtype. Against the pad / shifted-add / `jnp.power` expression it
    replaces (kept as the oracle in tests/test_layers.py) it differs by
    a reassociated window sum and one exp/log pair for `power`: a few
    ulp, held to 1e-5 relative there, forward and gradient."""
    return x * _scale_pow(x, size, alpha, beta, k)[1]


def _fwd(x, size, alpha, beta, k):
    return x * _scale_pow(x, size, alpha, beta, k)[1], x


def _bwd(size, alpha, beta, k, x, dy):
    scale, inv = _scale_pow(x, size, alpha, beta, k)
    inner = window_sum(dy * x * inv / scale, size)
    return (dy * inv - (2.0 * alpha * beta / size) * x * inner,)


lrn_across_channels.defvjp(_fwd, _bwd)
