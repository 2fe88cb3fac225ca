"""Convolution primitives with Caffe shape/layout conventions.

Reference: src/caffe/layers/base_conv_layer.cpp (im2col engine) and
src/caffe/layers/cudnn_conv_layer.cpp (cuDNN engine with FindEx algorithm
auto-seeking, workspace budgeting, group parallelism — 1,009 LoC).

On TPU all of that collapses into `lax.conv_general_dilated`: XLA selects the
MXU tiling (no algo seeker), fuses bias/activation consumers, and handles
groups natively (`feature_group_count`). Layouts follow Caffe logically —
activations NCHW, weights OIHW (out, in/group, kh, kw) — while XLA's TPU
layout assignment picks the physical tiling, so no manual NHWC conversion
is needed.

Output dim: floor((H + 2p - ((k-1)*dilation + 1)) / s) + 1 — conv uses floor
(conv_layer.cpp compute_output_shape), unlike pooling's ceil.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

DN = lax.conv_dimension_numbers


def conv_output_dim(size: int, kernel: int, pad: int, stride: int, dilation: int) -> int:
    kernel_ext = dilation * (kernel - 1) + 1
    return (size + 2 * pad - kernel_ext) // stride + 1


def conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: tuple[int, int],
           pad: tuple[int, int], dilation: tuple[int, int] = (1, 1),
           groups: int = 1, precision: str | None = None) -> jnp.ndarray:
    """x: (N, Cin, H, W); w: (Cout, Cin/groups, kh, kw) -> (N, Cout, oh, ow)."""
    dn = DN(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w,
        window_strides=stride,
        padding=(( pad[0], pad[0]), (pad[1], pad[1])),
        rhs_dilation=dilation,
        dimension_numbers=dn,
        feature_group_count=groups,
        precision=precision,
    )


def deconv2d(x: jnp.ndarray, w: jnp.ndarray, stride: tuple[int, int],
             pad: tuple[int, int], dilation: tuple[int, int] = (1, 1),
             groups: int = 1, precision: str | None = None) -> jnp.ndarray:
    """Transposed conv (reference deconv_layer.cpp: backward-of-conv as
    forward). x: (N, Cin, H, W); w: (Cin, Cout/groups, kh, kw) — Caffe keeps
    the conv weight layout with the roles of the feature dims swapped.

    Output dim: s*(H-1) + ((k-1)*d + 1) - 2p  (deconv compute_output_shape).
    Implemented as the transpose of conv2d via input dilation."""
    kh, kw = w.shape[2], w.shape[3]
    kh_ext = dilation[0] * (kh - 1) + 1
    kw_ext = dilation[1] * (kw - 1) + 1
    if groups != 1:
        # grouped deconv: split features, run per group, concat
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(w, groups, axis=0)
        return jnp.concatenate(
            [deconv2d(xi, wi, stride, pad, dilation, 1, precision)
             for xi, wi in zip(xs, ws)],
            axis=1,
        )
    # conv_transpose with flipped kernel reproduces gradient-of-conv exactly
    w_t = jnp.flip(w, axis=(2, 3)).transpose(1, 0, 2, 3)  # -> (Cout, Cin, kh, kw)
    dn = DN(x.shape, w_t.shape, ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w_t,
        window_strides=(1, 1),
        padding=((kh_ext - 1 - pad[0], kh_ext - 1 - pad[0]),
                 (kw_ext - 1 - pad[1], kw_ext - 1 - pad[1])),
        lhs_dilation=stride,
        rhs_dilation=dilation,
        dimension_numbers=dn,
        precision=precision,
    )


def im2col(x: jnp.ndarray, kernel: tuple[int, int], stride: tuple[int, int],
           pad: tuple[int, int], dilation: tuple[int, int] = (1, 1)) -> jnp.ndarray:
    """Patch extraction (reference util/im2col.cu): (N,C,H,W) ->
    (N, C*kh*kw, oh, ow). Exposed as the Im2col layer; XLA lowers it to a
    gather rather than a materialized GEMM operand, so unlike the reference
    it is not the conv engine — conv2d goes straight to the MXU."""
    c = x.shape[1]
    patches = lax.conv_general_dilated_patches(
        x,
        filter_shape=kernel,
        window_strides=stride,
        padding=((pad[0], pad[0]), (pad[1], pad[1])),
        rhs_dilation=dilation,
        dimension_numbers=DN(x.shape, (1, 1, *kernel), ("NCHW", "OIHW", "NCHW")),
    )
    return patches
