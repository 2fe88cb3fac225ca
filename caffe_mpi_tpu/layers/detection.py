"""DetectNetTransformation layer — detection augmentation as a net layer.

Reference: src/caffe/layers/detectnet_transform_layer.{cpp,cu} (753+268
LoC) + util/detectnet_coverage_rectangular.cpp, used by
examples/kitti/detectnet_network.prototxt:65-127: bottoms (data, label)
from the DIGITS-format image/label DBs, tops (transformed_data,
transformed_label) where the label becomes the stride-decimated coverage
grid [coverage, dx1, dy1, dx2, dy2] per class.

TPU-native design: the augmentation is branchy per-record host work
(random crop/flip/hue on variable bbox lists), exactly what should NOT be
traced into the XLA step — so the layer executes the existing host
pipeline (data/detectnet.py DetectNetAugmenter + coverage_label, the same
code the DetectNetFeeder uses) through `jax.pure_callback`. The callback
is driven by the per-iteration rng key, so training stays reproducible;
outputs are static-shape (the grid is fixed by image_size/stride), which
keeps the surrounding jit program static. Gradients stop here (the
reference's layer is equally non-differentiable: it feeds data).

Label wire format (blobToLabels, detectnet_transform_layer.cpp:199-219):
per record a flat float list [numBboxes, bboxLen(=16), <numBboxes x 16
fields>] where each 16-field row is [x, y, w, h, alpha, class, ...]
(include/caffe/util/detectnet_coverage.hpp:21-50).
"""

from __future__ import annotations

import logging

import numpy as np
import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)

from ..proto.config import (
    DetectNetAugmentationParameter,
    DetectNetGroundTruthParameter,
)
from .base import Layer, Shape, register

BBOX_LEN = 16  # sizeof(BboxLabel)/sizeof(Dtype) in the reference


def parse_label_blob(rec: np.ndarray) -> np.ndarray:
    """One record's label blob (any shape, flattened) -> (n, 5) bboxes
    [cls, x1, y1, x2, y2]. Mirrors blobToLabels + the Rect(x,y,w,h) ->
    corners conversion the coverage generator performs (bbox.br())."""
    flat = np.asarray(rec, np.float32).reshape(-1)
    n = int(flat[0])
    blen = int(flat[1])
    if blen == 0:
        blen = BBOX_LEN  # header row of an empty record may be all-zero
    elif blen != BBOX_LEN:
        # reference: CHECK_EQ(bboxLen, sizeof(BboxLabel)/sizeof(Dtype)),
        # detectnet_transform_layer.cpp:212 — misaligned rows would
        # silently scramble classes/coordinates
        raise ValueError(f"label record declares bboxLen {blen}, "
                         f"expected {BBOX_LEN}")
    rows = flat[blen: blen + n * blen].reshape(n, blen)
    out = np.zeros((n, 5), np.float32)
    out[:, 0] = rows[:, 5]                    # classNumber
    out[:, 1] = rows[:, 0]                    # x1
    out[:, 2] = rows[:, 1]                    # y1
    out[:, 3] = rows[:, 0] + rows[:, 2]       # x + w
    out[:, 4] = rows[:, 1] + rows[:, 3]       # y + h
    return out


def encode_label_blob(bboxes: np.ndarray, max_bboxes: int) -> np.ndarray:
    """Inverse of parse_label_blob for fixtures/datasets: (n,5) corner
    bboxes -> (1, max_bboxes + 1, 16) DIGITS-format label blob."""
    bboxes = np.asarray(bboxes, np.float32).reshape(-1, 5)
    n = len(bboxes)
    if n > max_bboxes:
        raise ValueError(f"{n} bboxes > max {max_bboxes}")
    out = np.zeros((1, max_bboxes + 1, BBOX_LEN), np.float32)
    out[0, 0, 0] = n
    out[0, 0, 1] = BBOX_LEN
    out[0, 1:1 + n, 0] = bboxes[:, 1]
    out[0, 1:1 + n, 1] = bboxes[:, 2]
    out[0, 1:1 + n, 2] = bboxes[:, 3] - bboxes[:, 1]
    out[0, 1:1 + n, 3] = bboxes[:, 4] - bboxes[:, 2]
    out[0, 1:1 + n, 5] = bboxes[:, 0]
    return out


@register("DetectNetTransformation")
class DetectNetTransformationLayer(Layer):
    # tells the Solver the compiled step re-enters Python mid-execution:
    # on the single-slot CPU runtime the driver must not dispatch further
    # work (which waits on the busy pool WHILE holding the GIL the
    # callback needs) until the step completes
    host_callback = True

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        if len(in_shapes) != 2:
            raise ValueError(
                f"layer {self.name!r}: DetectNetTransformation takes "
                "(data, label) bottoms")
        gt = (self.lp.detectnet_groundtruth_param
              or DetectNetGroundTruthParameter())
        self.gt = gt
        self.aug = (self.lp.detectnet_augmentation_param
                    or DetectNetAugmentationParameter())
        # class mapping: dataset ids -> contiguous coverage indices
        self.class_map = {m.src: m.dst for m in gt.object_class} or {1: 0}
        self.num_classes = max(self.class_map.values()) + 1
        n = in_shapes[0][0]
        if in_shapes[1][0] != n:
            raise ValueError(
                f"layer {self.name!r}: data batch {n} != label batch "
                f"{in_shapes[1][0]} (detectnet_transform_layer.cpp:116)")
        if in_shapes[0][1] != 3:
            raise ValueError(
                f"layer {self.name!r}: expects 3-channel images, got "
                f"{in_shapes[0][1]} (detectnet_transform_layer.cpp:115 "
                "CHECK_EQ(channels, 3))")
        tp = self.lp.transform_param
        self.mean_values = list(tp.mean_value) if tp else []
        channels = in_shapes[0][1]
        if len(self.mean_values) not in (0, 1, channels):
            # the reference's retrieveMeanChannels switch handles only 1
            # or C values and silently does nothing otherwise; raising
            # beats silently mis-broadcasting
            raise ValueError(
                f"layer {self.name!r}: {len(self.mean_values)} mean_value "
                f"entries for {channels} channels (expected 1 or "
                f"{channels})")
        if len(self.mean_values) == 1:
            self.mean_values = self.mean_values * channels
        # import the host pipeline NOW (main thread): first-import work
        # happening later on the XLA callback thread can deadlock the
        # single-core CPU runtime. No jax backend query here — setup must
        # stay shape-only (a backend probe would start the
        # device backend for pure shape flows like `summarize`).
        from ..data.detectnet import DetectNetAugmenter, coverage_label
        self._augmenter = DetectNetAugmenter(self.aug, gt, self.phase)
        self._coverage_label = coverage_label
        self._mean = (np.asarray(self.mean_values, np.float32)
                      if self.mean_values else None)
        # lint: ok(thread-shared-mutation) — setup() completes before
        # the graph (and its callbacks) can run; no thread exists yet
        self._warned_single_slot = False
        gh, gw = gt.image_size_y // gt.stride, gt.image_size_x // gt.stride
        self._out_shapes = [(n, 3, gt.image_size_y, gt.image_size_x),
                            (n, self.num_classes * 5, gh, gw)]
        return list(self._out_shapes)

    def _host_transform(self, data, label, seed) -> tuple[np.ndarray, np.ndarray]:
        # operands may arrive as jax.Arrays (zero-copy on CPU); convert
        # WHOLESALE first — indexing a jax.Array here would dispatch a new
        # XLA slice onto the executor that is currently blocked waiting
        # for this very callback (single-slot CPU runtime deadlock)
        data = np.asarray(data, np.float32)
        label = np.asarray(label)
        seed = int(seed)
        if not self._warned_single_slot:
            # lint: ok(thread-shared-mutation) — setup() runs before the
            # first callback can fire; a lost race between callback
            # threads costs one duplicated warning, nothing more
            self._warned_single_slot = True
            if (jax.default_backend() == "cpu"
                    and len(jax.local_devices()) < 2):
                log.warning(
                    "DetectNetTransformation on a single-device CPU "
                    "backend: jax.pure_callback's internal device_put can "
                    "deadlock the lone execution slot. Set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=2 (before jax "
                    "initializes) to give the callback a free slot.")
        augmenter = self._augmenter
        coverage_label = self._coverage_label
        imgs, covs = [], []
        for i in range(data.shape[0]):
            rng = np.random.Generator(np.random.Philox(
                key=(seed << 32) ^ i))
            raw = parse_label_blob(label[i])
            # dataset class ids -> coverage indices; unmapped ids drop
            # (reference: classes absent from object_class are ignored)
            mapped = [np.concatenate(([[self.class_map[int(b[0])]]], [b[1:]]),
                                     axis=None)
                      for b in raw if int(b[0]) in self.class_map]
            boxes = (np.stack(mapped) if mapped
                     else np.zeros((0, 5), np.float32))
            # mean goes through the augmenter so the crop's zero-pad sits
            # in mean-subtracted space (reference transform_image_cpu:
            # meanSubtract before crop_image_cpu)
            img, boxes = augmenter(data[i], boxes, rng, mean=self._mean)
            imgs.append(img)
            covs.append(coverage_label(boxes, self.gt, self.num_classes))
        return (np.stack(imgs).astype(np.float32),
                np.stack(covs).astype(np.float32))

    def apply(self, params, state, bottoms, *, train, rng):
        data, label = bottoms[0], bottoms[1]
        seed = (jax.random.randint(rng, (), 0, np.int32(2**31 - 1))
                if (train and rng is not None) else jnp.int32(0))
        out_img, out_cov = jax.pure_callback(
            self._host_transform,
            (jax.ShapeDtypeStruct(self._out_shapes[0], jnp.float32),
             jax.ShapeDtypeStruct(self._out_shapes[1], jnp.float32)),
            data, label, seed, vmap_method="sequential")
        # data path, like the reference's: no gradients flow upstream
        return [jax.lax.stop_gradient(self.f(out_img)),
                jax.lax.stop_gradient(out_cov)], state
