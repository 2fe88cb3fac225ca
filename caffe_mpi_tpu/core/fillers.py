"""Parameter initializers — functional equivalents of the reference fillers.

The reference's Filler hierarchy (include/caffe/filler.hpp) mutates a Blob in
place from a `FillerParameter`; here each filler is a pure function
`(key, shape, dtype) -> array`, driven by the same FillerParameter schema so
prototxt weight_filler/bias_filler blocks behave identically.

Fan-in/fan-out conventions match filler.hpp: for a weight of shape
(out, in, kh, kw), fan_in = count/out = in*kh*kw and fan_out = count/in.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..proto.config import FillerParameter


def _fans(shape: tuple[int, ...]) -> tuple[float, float]:
    count = math.prod(shape)
    num = shape[0] if shape else 1
    channels = shape[1] if len(shape) > 1 else 1
    fan_in = count / num if num else 1
    fan_out = count / channels if channels else 1
    return fan_in, fan_out


def _scale_n(filler: FillerParameter, shape) -> float:
    fan_in, fan_out = _fans(shape)
    norm = filler.variance_norm.upper()
    if norm == "FAN_OUT":
        return fan_out
    if norm == "AVERAGE":
        return (fan_in + fan_out) / 2.0
    return fan_in


def fill(filler: FillerParameter | None, key: jax.Array, shape: tuple[int, ...],
         dtype=jnp.float32) -> jax.Array:
    """Create an initialized parameter array per the filler spec."""
    if filler is None:
        filler = FillerParameter()
    if filler.tile > 1:
        # the first 1/tile of the last axis, repeated
        if not shape or shape[-1] % filler.tile:
            raise ValueError(f"filler tile {filler.tile} does not divide "
                             f"the last axis of {shape}")
        part = fill(dataclasses.replace(filler, tile=1), key,
                    (*shape[:-1], shape[-1] // filler.tile), dtype)
        return jnp.tile(part, (1,) * (len(shape) - 1) + (filler.tile,))
    if filler.mirror:
        # the first half of the last axis, then its negative
        if not shape or shape[-1] % 2:
            raise ValueError(f"filler mirror needs an even last axis, not "
                             f"{shape}")
        part = fill(dataclasses.replace(filler, mirror=False), key,
                    (*shape[:-1], shape[-1] // 2), dtype)
        return jnp.concatenate([part, -part], axis=-1)
    ftype = filler.type
    if ftype == "constant":
        return jnp.full(shape, filler.value, dtype)
    if ftype == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, filler.min,
                                  filler.max).astype(dtype)
    if ftype == "gaussian":
        out = filler.mean + filler.std * jax.random.normal(key, shape, jnp.float32)
        # sparse option (filler.hpp GaussianFiller): keep each output unit's
        # weights with prob sparse/fan_in, zero the rest
        if filler.sparse > 0:
            fan_in, _ = _fans(shape)
            prob = min(1.0, filler.sparse / max(fan_in, 1))
            mask = jax.random.bernoulli(jax.random.fold_in(key, 1), prob, shape)
            out = jnp.where(mask, out, 0.0)
        return out.astype(dtype)
    if ftype == "log_arange":
        # ln(i + 1) along the last axis (a state-space mixer's A_log)
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=jnp.float32)), shape).astype(dtype)
    if ftype == "softplus_inverse_log_uniform":
        # v with softplus(v) log-uniform in [min, max], floored at `value`
        # (a state-space mixer's dt_bias): v = t + ln(1 - exp(-t))
        t = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       math.log(filler.min),
                                       math.log(filler.max)))
        t = jnp.maximum(t, filler.value)
        return (t + jnp.log(-jnp.expm1(-t))).astype(dtype)
    if ftype == "xavier":
        scale = math.sqrt(3.0 / _scale_n(filler, shape))
        return jax.random.uniform(key, shape, jnp.float32, -scale,
                                  scale).astype(dtype)
    if ftype == "msra":
        std = math.sqrt(2.0 / _scale_n(filler, shape))
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if ftype == "positive_unitball":
        x = jax.random.uniform(key, shape, jnp.float32)
        flat = x.reshape(shape[0], -1)
        flat = flat / jnp.sum(flat, axis=1, keepdims=True)
        return flat.reshape(shape).astype(dtype)
    if ftype == "bilinear":
        # upsampling kernel for Deconvolution (filler.hpp BilinearFiller)
        if len(shape) != 4 or shape[2] != shape[3]:
            raise ValueError("bilinear filler requires square 4D kernels")
        k = shape[3]
        f = math.ceil(k / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        og = np.ogrid[:k, :k]
        kern = (1 - abs(og[0] / f - c)) * (1 - abs(og[1] / f - c))
        return jnp.broadcast_to(jnp.asarray(kern, jnp.float32), shape).astype(dtype)
    raise ValueError(f"unknown filler type {ftype!r}")
