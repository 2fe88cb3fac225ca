"""In-graph (device-side) data augmentation — crop/mean/mirror/scale fused
into the jitted step.

Reference: src/caffe/data_transformer.cu (TransformKernel: one CUDA thread
per output element applying crop window, mean subtraction, mirror and
scale on the GPU) and include/caffe/layers/base_data_layer.hpp:111-116
(`use_gpu_transform`, default-on for fp16 forward types): the reference
moves the transform to the accelerator because the host cannot feed a fast
chip. The TPU-native equivalent stages the *uint8* batch to HBM (4x less
host->device traffic than transformed f32, and the host link is the
scarce resource) together with a tiny (B,3) int32 tensor of augmentation
decisions, and performs crop + mean + mirror + scale inside the jitted
train step where XLA fuses them into the first conv's input pipeline.

The augmentation DECISIONS stay on the host: they come from the same
per-record Philox streams as the host DataTransformer (transformer.py), so
the device path is bit-compatible with the host path and deterministic
regardless of which path runs — this mirrors how the reference keeps
curand out of it and draws on the CPU (data_transformer.cpp Rand) while
transforming on the GPU.

Operation order matches the host/reference exactly:
  out = mirror(crop(img) - crop(mean)) * scale
(the mean window is the unmirrored crop window; mirroring happens after
subtraction — data_transformer.cpp Transform).
"""

from __future__ import annotations

import numpy as np

AUG_FIELDS = 3  # off_h, off_w, mirror — per-record int32

def aug_key(top: str) -> str:
    """Feed-dict key for a data top's augmentation decisions."""
    return f"{top}__aug"


def compute_aug(tf, flats, in_hw, batch: int) -> np.ndarray:
    """Host-side decision kernel: (B,3) int32 [off_h, off_w, mirror].

    `tf` is the host DataTransformer; draws replay its exact RNG call
    sequence (off_h, off_w, then mirror, from the per-record Philox
    stream), so device and host transforms of the same record agree."""
    tp = tf.tp
    h, w = in_hw
    crop = tp.crop_size
    train = tf.phase == "TRAIN"
    out = np.zeros((batch, AUG_FIELDS), np.int32)
    if crop and not train:
        out[:, 0] = (h - crop) // 2
        out[:, 1] = (w - crop) // 2
    draws_needed = train and (crop or tp.mirror)
    if draws_needed:
        for i, flat in enumerate(flats):
            rng = tf.record_rng(int(flat))
            if crop:
                out[i, 0] = rng.integers(0, h - crop + 1)
                out[i, 1] = rng.integers(0, w - crop + 1)
            if tp.mirror:
                out[i, 2] = rng.integers(2)
    return out


def device_transform(raw, aug, *, crop: int, mean, scale: float):
    """The jittable transform: raw (B,C,H,W) uint8, aug (B,3) int32 ->
    (B,C,crop,crop) float32 (or (B,C,H,W) without crop).

    mean: None, a per-channel (C,1,1) array, or a full-size (C,H,W) array
    (cropped at the same per-record window, like the reference's
    mean_file path). Closed over as a compile-time constant."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, c, h, w = raw.shape
    if crop:
        def crop_one(img, oh, ow):
            return lax.dynamic_slice(img, (0, oh, ow), (c, crop, crop))
        x = jax.vmap(crop_one)(raw, aug[:, 0], aug[:, 1])
    else:
        x = raw
    x = x.astype(jnp.float32)

    if mean is not None:
        m = jnp.asarray(mean, jnp.float32)
        if crop and m.ndim == 3 and m.shape[-2:] == (h, w):
            def crop_mean(oh, ow):
                return lax.dynamic_slice(m, (0, oh, ow), (c, crop, crop))
            x = x - jax.vmap(crop_mean)(aug[:, 0], aug[:, 1])
        else:
            x = x - m  # (C,1,1) channel means broadcast; or full, no crop

    mirrored = x[..., ::-1]
    x = jnp.where(aug[:, 2, None, None, None] > 0, mirrored, x)

    if scale != 1.0:
        x = x * scale
    return x


def wants_device_transform(lp) -> bool:
    """Resolve the per-layer device-transform request.

    Mirrors base_data_layer.hpp:111-116: an explicit
    transform_param.use_gpu_transform wins; unset defaults to ON (the
    reference defaults on only for fp16 forward types — on TPU the fused
    path is the right default whenever it is expressible).
    force_color/force_gray change the channel count on the host decode
    side and stay host-only, as in the reference (encoded datums force
    copy_to_cpu, data_layer.cpp:243)."""
    tp = lp.transform_param
    if tp is not None and (tp.force_color or tp.force_gray):
        return False
    if tp is not None and tp.has("use_gpu_transform"):
        return bool(tp.use_gpu_transform)
    return True
