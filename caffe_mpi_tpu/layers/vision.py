"""Spatial layers: Convolution, Deconvolution, Pooling, LRN, Im2col, Crop, SPP.

Reference implementations: src/caffe/layers/{base_conv,conv,deconv,pooling,
lrn,im2col,crop,spp}_layer.{cpp,cu} + cudnn variants. The cuDNN engine
machinery (algo auto-seek, workspace budgets, group streams) has no TPU
counterpart — XLA owns those decisions — so each layer is only Caffe shape
semantics + a lax primitive call.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.conv import conv2d, conv_output_dim, deconv2d, im2col
from ..ops.pool import avg_pool2d, max_pool2d, pool_output_dim
from ..proto.config import ConvolutionParameter, FillerParameter
from .base import Layer, Shape, register


def _spatial_params(p: ConvolutionParameter) -> tuple[tuple, tuple, tuple, tuple]:
    """Resolve Caffe's repeated kernel_size/stride/pad + legacy _h/_w fields
    (base_conv_layer.cpp LayerSetUp)."""
    def resolve(rep: list[int], h: int, w: int, default: int) -> tuple[int, int]:
        if h or w:
            return (h, w)
        if not rep:
            return (default, default)
        if len(rep) == 1:
            return (rep[0], rep[0])
        return (rep[0], rep[1])

    kernel = resolve(p.kernel_size, p.kernel_h, p.kernel_w, 0)
    stride = resolve(p.stride, p.stride_h, p.stride_w, 1)
    pad = resolve(p.pad, p.pad_h, p.pad_w, 0)
    dil = tuple(p.dilation) * (2 // max(len(p.dilation), 1)) if p.dilation else (1, 1)
    if len(dil) == 1:
        dil = (dil[0], dil[0])
    if kernel[0] <= 0 or kernel[1] <= 0:
        raise ValueError("convolution kernel_size must be positive")
    return kernel, stride, pad, dil


@register("Convolution")
class ConvolutionLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.convolution_param or ConvolutionParameter()
        self.p = p
        self.kernel, self.stride, self.pad, self.dilation = _spatial_params(p)
        n, cin, h, w = in_shapes[0]
        if cin % p.group or p.num_output % p.group:
            raise ValueError(f"{self.name}: channels not divisible by group")
        self.declare("weight",
                     (p.num_output, cin // p.group, *self.kernel),
                     p.weight_filler)
        if p.bias_term:
            self.declare("bias", (p.num_output,),
                         p.bias_filler or FillerParameter(type="constant"))
        oh = conv_output_dim(h, self.kernel[0], self.pad[0], self.stride[0], self.dilation[0])
        ow = conv_output_dim(w, self.kernel[1], self.pad[1], self.stride[1], self.dilation[1])
        return [(n, p.num_output, oh, ow)]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        w = self.f(params["weight"])
        y = conv2d(x, w, self.stride, self.pad, self.dilation, self.p.group,
                   precision=self.policy.lax_precision)
        if self.p.bias_term:
            y = y + self.f(params["bias"])[None, :, None, None]
        return [y], state


@register("Deconvolution")
class DeconvolutionLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.convolution_param or ConvolutionParameter()
        self.p = p
        self.kernel, self.stride, self.pad, self.dilation = _spatial_params(p)
        n, cin, h, w = in_shapes[0]
        # Caffe deconv weight shape: (Cin, Cout/group, kh, kw) — conv layout
        # with the feature roles swapped (deconv_layer.cpp).
        self.declare("weight",
                     (cin, p.num_output // p.group, *self.kernel),
                     p.weight_filler)
        if p.bias_term:
            self.declare("bias", (p.num_output,),
                         p.bias_filler or FillerParameter(type="constant"))
        kh_ext = self.dilation[0] * (self.kernel[0] - 1) + 1
        kw_ext = self.dilation[1] * (self.kernel[1] - 1) + 1
        oh = self.stride[0] * (h - 1) + kh_ext - 2 * self.pad[0]
        ow = self.stride[1] * (w - 1) + kw_ext - 2 * self.pad[1]
        return [(n, p.num_output, oh, ow)]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        w = self.f(params["weight"])
        y = deconv2d(x, w, self.stride, self.pad, self.dilation, self.p.group,
                     precision=self.policy.lax_precision)
        if self.p.bias_term:
            y = y + self.f(params["bias"])[None, :, None, None]
        return [y], state


@register("Pooling")
class PoolingLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.pooling_param
        self.p = p
        n, c, h, w = in_shapes[0]
        if p.global_pooling:
            self.kernel = (h, w)
            self.stride = (1, 1)
            self.pad = (0, 0)
        else:
            kh = p.kernel_h or p.kernel_size
            kw = p.kernel_w or p.kernel_size
            if kh <= 0 or kw <= 0:
                raise ValueError(f"{self.name}: pooling kernel_size required")
            self.kernel = (kh, kw)
            self.stride = (p.stride_h or p.stride, p.stride_w or p.stride)
            self.pad = (p.pad_h or p.pad, p.pad_w or p.pad)
        any_pad = self.pad[0] > 0 or self.pad[1] > 0
        oh = pool_output_dim(h, self.kernel[0], self.pad[0], self.stride[0], any_pad)
        ow = pool_output_dim(w, self.kernel[1], self.pad[1], self.stride[1], any_pad)
        self.method = str(p.pool).upper()
        if self.method == "STOCHASTIC" and (self.pad[0] or self.pad[1]):
            raise ValueError("STOCHASTIC pooling does not support padding "
                             "(reference pooling_layer.cpp CHECKs the same)")
        return [(n, c, oh, ow)]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        if self.method == "AVE":
            y = avg_pool2d(x, self.kernel, self.stride, self.pad)
        elif self.method == "STOCHASTIC":
            y = self._stochastic(x, train, rng)
        else:
            y = max_pool2d(x, self.kernel, self.stride, self.pad)
        return [y], state

    def _stochastic(self, x, train, rng):
        """Stochastic pooling (pooling_layer.cpp:239-300): TRAIN samples a
        window element with probability proportional to its (non-negative)
        activation; TEST returns the activation-weighted average
        sum(a^2)/sum(a)."""
        from ..ops.conv import DN
        from ..ops.pool import _pad_amounts, pool_output_dim
        n, c, h, w = x.shape
        kh, kw = self.kernel
        # ceil-mode output dims like MAX/AVE: zero-pad the high side; zeros
        # carry zero sampling weight, reproducing the reference's window
        # truncation at the boundary
        oh = pool_output_dim(h, kh, 0, self.stride[0])
        ow = pool_output_dim(w, kw, 0, self.stride[1])
        ph = _pad_amounts(h, kh, 0, self.stride[0], oh)
        pw = _pad_amounts(w, kw, 0, self.stride[1], ow)
        patches = lax.conv_general_dilated_patches(
            x, filter_shape=(kh, kw), window_strides=self.stride,
            padding=(ph, pw),
            dimension_numbers=DN(x.shape, (1, 1, kh, kw),
                                 ("NCHW", "OIHW", "NCHW")))
        oh, ow = patches.shape[2], patches.shape[3]
        pat = patches.reshape(n, c, kh * kw, oh, ow)
        total = jnp.sum(pat, axis=2)
        if train:
            if rng is None:
                raise ValueError(f"{self.name}: stochastic pooling needs rng")
            r = jax.random.uniform(rng, (n, c, oh, ow)) * total
            cum = jnp.cumsum(pat, axis=2)
            idx = jnp.argmax(cum >= r[:, :, None], axis=2)
            y = jnp.take_along_axis(pat, idx[:, :, None], axis=2)[:, :, 0]
            return jnp.where(total > 0, y, 0.0)
        sq = jnp.sum(pat * pat, axis=2)
        return jnp.where(total > 0, sq / jnp.maximum(total, 1e-12), 0.0)


@register("LRN")
class LRNLayer(Layer):
    """Local response normalization (lrn_layer.cpp):
    y = x * (k + (alpha/n) * sum_window(x^2))^(-beta)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.lrn_param
        if p is None:
            from ..proto.config import LRNParameter
            p = LRNParameter()
        if p.local_size % 2 != 1:
            raise ValueError("LRN local_size must be odd")
        self.p = p
        self.region = str(p.norm_region).upper()
        return [in_shapes[0]]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        p = self.p
        # ISSUE 9: the across-channels case routes through the Pallas
        # kernels (ops/lrn.py — fwd + custom_vjp bwd, one HBM pass per
        # direction) whenever the layer COMPUTES in bf16 — keyed on the
        # input dtype, so both the `precision: bf16` solver knob and the
        # pre-existing FLOAT16 prototxt variants (solver_fp16 recipes)
        # take the kernels (in-kernel math is f32). Float32 takes the lax
        # path of ops/lrn_lax.py: the same mathematics, no Pallas import.
        # Not bitwise the pad / shifted-add / jnp.power expression that
        # stood here before PR 39: a reassociated window sum and one
        # exp/log pair for `power`, a few ulp; tests/test_layers.py holds
        # it to 1e-5 relative of that expression, forward and gradient.
        use_pallas = (self.region != "WITHIN_CHANNEL" and x.ndim == 4
                      and x.dtype == jnp.bfloat16)
        if use_pallas:
            from ..ops.lrn import lrn_across_channels

            def kernel(x):
                return lrn_across_channels(x, p.local_size, p.alpha,
                                           p.beta, p.k)
            if self.mesh_plan is not None:
                # inside the solver's GSPMD step a Mosaic call must be
                # partitioned by hand; LRN is per-sample
                return [self.mesh_plan.per_batch_shard(kernel, x)], state
            return [kernel(x)], state
        if self.region != "WITHIN_CHANNEL":
            from ..ops.lrn_lax import lrn_across_channels
            return [lrn_across_channels(x, p.local_size, p.alpha, p.beta,
                                        p.k)], state
        # spatial window, divisor is the full window size (lrn pads with 0)
        half = (p.local_size - 1) // 2
        window_sum = lax.reduce_window(
            jnp.square(x), np.zeros((), np.dtype(x.dtype))[()], lax.add,
            window_dimensions=(1, 1, p.local_size, p.local_size),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (0, 0), (half, half), (half, half)),
        )
        scale = p.k + window_sum * (p.alpha / (p.local_size * p.local_size))
        return [x * jnp.power(scale, -p.beta)], state


@register("Im2col")
class Im2colLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        from ..proto.config import ConvolutionParameter as CP
        p = self.lp.convolution_param or CP()
        self.kernel, self.stride, self.pad, self.dilation = _spatial_params(p)
        n, c, h, w = in_shapes[0]
        oh = conv_output_dim(h, self.kernel[0], self.pad[0], self.stride[0], self.dilation[0])
        ow = conv_output_dim(w, self.kernel[1], self.pad[1], self.stride[1], self.dilation[1])
        return [(n, c * self.kernel[0] * self.kernel[1], oh, ow)]

    def apply(self, params, state, bottoms, *, train, rng):
        y = im2col(self.f(bottoms[0]), self.kernel, self.stride, self.pad,
                   self.dilation)
        return [y], state


@register("Crop")
class CropLayer(Layer):
    """Crop bottom[0] to bottom[1]'s shape from `axis` on, at `offset`
    (crop_layer.cpp)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.crop_param
        axis = p.axis if p else 2
        offsets = list(p.offset) if p else []
        a, b = in_shapes[0], in_shapes[1]
        out = list(a)
        self.starts = [0] * len(a)
        for i in range(axis, len(a)):
            off = 0
            if offsets:
                off = offsets[i - axis] if len(offsets) > 1 else offsets[0]
            if off + b[i] > a[i]:
                raise ValueError(f"{self.name}: crop exceeds bottom size on axis {i}")
            self.starts[i] = off
            out[i] = b[i]
        self.out = tuple(out)
        return [self.out]

    def apply(self, params, state, bottoms, *, train, rng):
        x = bottoms[0]
        y = lax.dynamic_slice(x, tuple(self.starts), self.out)
        return [y], state


@register("SPP")
class SPPLayer(Layer):
    """Spatial pyramid pooling (spp_layer.cpp): pyramid of global-ish max/ave
    pools flattened+concatenated."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.spp_param
        self.height = p.pyramid_height
        self.method = str(p.pool).upper() if p else "MAX"
        n, c, h, w = in_shapes[0]
        self.levels = []
        total = 0
        import math
        for l in range(self.height):
            bins = 2 ** l
            kh, kw = math.ceil(h / bins), math.ceil(w / bins)
            ph = (kh * bins - h + 1) // 2
            pw = (kw * bins - w + 1) // 2
            self.levels.append(((kh, kw), (kh, kw), (ph, pw), bins))
            total += c * bins * bins
        return [(n, total)]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0])
        n = x.shape[0]
        outs = []
        for (kernel, stride, pad, bins) in self.levels:
            if self.method == "AVE":
                y = avg_pool2d(x, kernel, stride, pad)
            else:
                y = max_pool2d(x, kernel, stride, pad)
            outs.append(y[:, :, :bins, :bins].reshape(n, -1))
        return [jnp.concatenate(outs, axis=1)], state
