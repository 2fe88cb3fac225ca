"""Loss layers + Softmax + Accuracy.

Reference: src/caffe/layers/{softmax,softmax_loss,euclidean_loss,l1_loss,
sigmoid_cross_entropy_loss,hinge_loss,infogain_loss,contrastive_loss,
multinomial_logistic_loss,accuracy,loss}_layer.{cpp,cu}.

Loss semantics that affect convergence parity and are reproduced exactly:
- normalization modes FULL/VALID/BATCH_SIZE/NONE (loss_layer.cpp
  GetNormalizer; VALID is the default — divide by the count of non-ignored
  targets).
- ignore_label masking in softmax loss and accuracy.
- every loss layer's top is a scalar; the Net multiplies by loss_weight.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .base import Layer, Shape, register


def _softmax_axis(lp, nd: int) -> int:
    axis = lp.softmax_param.axis if lp.softmax_param else 1
    return axis % nd if axis < 0 else axis


@register("Softmax")
class SoftmaxLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        self.axis = _softmax_axis(self.lp, len(in_shapes[0]))
        return [in_shapes[0]]

    def apply(self, params, state, bottoms, *, train, rng):
        return [jax.nn.softmax(self.f(bottoms[0]), axis=self.axis)], state


class LossBase(Layer):
    def is_loss(self) -> bool:
        return True

    def default_loss_weight(self, top_idx: int) -> float:
        # first top of a *Loss layer carries weight 1 (layer.hpp SetLossWeights)
        return 1.0 if top_idx == 0 else 0.0

    def _norm_mode(self) -> str:
        p = self.lp.loss_param
        if p is None:
            return "VALID"
        # legacy flag (softmax_loss_layer.cpp:35-38): normalize:false means
        # BATCH_SIZE, normalize:true (or absent) means the modern default
        if not p.has("normalization") and p.has("normalize") and not p.normalize:
            return "BATCH_SIZE"
        return p.normalization

    def _ignore_label(self):
        p = self.lp.loss_param
        return p.ignore_label if p and p.has("ignore_label") else None


def _normalizer(mode: str, outer: int, full: int, valid):
    """loss_layer.cpp GetNormalizer. `valid` may be a traced scalar."""
    mode = mode.upper()
    if mode == "FULL":
        return float(full)
    if mode == "VALID":
        return jnp.maximum(valid.astype(jnp.float32), 1.0)
    if mode == "BATCH_SIZE":
        return float(outer)
    if mode == "NONE":
        return 1.0
    raise ValueError(f"unknown loss normalization {mode!r}")


def _label_terms(x, labels, axis, ignore, mode):
    """What both passes of `_softmax_nll` need of the labels: the 0/1 mark
    of each position's label along the class axis (a compare against an
    iota: XLA fuses it into its reader, nothing of the logits' size is
    written), the mask of counted positions, and the normaliser."""
    labels = jnp.expand_dims(
        labels.reshape(x.shape[:axis] + x.shape[axis + 1:]), axis)
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) == labels
    mask = jnp.ones(labels.shape, bool) if ignore is None else labels != ignore
    norm = _normalizer(mode, x.shape[0], labels.size, jnp.sum(mask))
    return hit, mask, norm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _softmax_nll(x, labels, weights, axis, ignore, mode):
    """Normalised sum over positions of -log softmax(x)[label], each times
    its weight where `weights` (one a position, constants of the step) is
    not None, in float32 from logits of any float type. Differentiated by
    jax this is a
    `take_along_axis` whose transpose zero-fills a float32 buffer of the
    logits' size and scatters into it, on top of a float32 log-softmax kept
    for the backward pass (1.24 GB and 14.6 ms a step in the language-model
    cell; PERF.md section 6, PR 28). Here the forward pass keeps the logits
    as they arrived and one log-sum-exp a position, and the backward pass
    is one fusion: (softmax - label mark) * mask * g / norm, rounded once."""
    return _softmax_nll_fwd(x, labels, weights, axis, ignore, mode)[0]


def _softmax_nll_fwd(x, labels, weights, axis, ignore, mode):
    hit, mask, norm = _label_terms(x, labels, axis, ignore, mode)
    xf = x.astype(jnp.float32)
    top = jnp.max(xf, axis=axis, keepdims=True)
    lse = top + jnp.log(jnp.sum(jnp.exp(xf - top), axis=axis, keepdims=True))
    picked = jnp.sum(jnp.where(hit, xf, 0.0), axis=axis, keepdims=True)
    nll = lse - picked
    if weights is not None:
        nll = nll * weights.astype(jnp.float32).reshape(mask.shape)
    loss = jnp.sum(jnp.where(mask, nll, 0.0)) / norm
    return loss, (x, labels, weights, lse)


def _softmax_nll_bwd(axis, ignore, mode, res, g):
    x, labels, weights, lse = res
    hit, mask, norm = _label_terms(x, labels, axis, ignore, mode)
    p = jnp.exp(x.astype(jnp.float32) - lse)
    scale = g / norm
    if weights is not None:
        scale = scale * weights.astype(jnp.float32).reshape(mask.shape)
    dx = jnp.where(mask, (p - hit) * scale, 0.0)
    return dx.astype(x.dtype), None, None


_softmax_nll.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


@register("SoftmaxWithLoss")
class SoftmaxWithLossLayer(LossBase):
    """Fused log-softmax + NLL (softmax_loss_layer.cpp). Second top, when
    requested, is the softmax output. A third bottom, when given, holds one
    weight a position (a TPU-native extension): the loss is sum(w * nll) /
    normalizer, ignore_label and the normalization modes as without it (FULL
    divides by every position); the weights are data and get no gradient."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        self.axis = _softmax_axis(self.lp, len(in_shapes[0]))
        if len(in_shapes) > 2 and math.prod(in_shapes[2]) \
                != math.prod(in_shapes[1]):
            raise ValueError(f"SoftmaxWithLoss weights {in_shapes[2]} are "
                             f"not one a label {in_shapes[1]}")
        tops = [()]
        if len(self.lp.top) > 1:
            tops.append(in_shapes[0])
        return tops

    def apply(self, params, state, bottoms, *, train, rng):
        logits = self.f(bottoms[0])
        weights = bottoms[2] if len(bottoms) > 2 else None
        tops = [_softmax_nll(logits, bottoms[1].astype(jnp.int32), weights,
                             self.axis, self._ignore_label(),
                             self._norm_mode())]
        if len(self.lp.top) > 1:
            tops.append(jax.nn.softmax(logits.astype(jnp.float32),
                                       axis=self.axis))
        return tops, state


@register("EuclideanLoss")
class EuclideanLossLayer(LossBase):
    """1/(2N) * sum((a-b)^2) (euclidean_loss_layer.cpp — normalizes by
    batch size only)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        a = self.f(bottoms[0]).astype(jnp.float32)
        b = self.f(bottoms[1]).astype(jnp.float32)
        n = a.shape[0]
        return [jnp.sum(jnp.square(a - b)) / (2.0 * n)], state


@register("L1Loss")
class L1LossLayer(LossBase):
    """sum(|a-b|)/N (NVCaffe l1_loss_layer.cpp)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        a = self.f(bottoms[0]).astype(jnp.float32)
        b = self.f(bottoms[1]).astype(jnp.float32) if len(bottoms) > 1 else 0.0
        n = a.shape[0]
        return [jnp.sum(jnp.abs(a - b)) / n], state


@register("SigmoidCrossEntropyLoss")
class SigmoidCrossEntropyLossLayer(LossBase):
    """Stable BCE-with-logits (sigmoid_cross_entropy_loss_layer.cpp);
    reference normalizes by batch size."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0]).astype(jnp.float32)
        t = self.f(bottoms[1]).astype(jnp.float32)
        # loss = max(x,0) - x*t + log(1+exp(-|x|))
        per = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
        ignore = self._ignore_label()
        if ignore is not None:
            mask = bottoms[1] != ignore
            per = jnp.where(mask, per, 0.0)
        return [jnp.sum(per) / x.shape[0]], state


@register("HingeLoss")
class HingeLossLayer(LossBase):
    """One-vs-all hinge on raw scores (hinge_loss_layer.cpp)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        x = self.f(bottoms[0]).astype(jnp.float32)
        labels = bottoms[1].astype(jnp.int32).reshape(-1)
        n, k = x.shape[0], x.shape[1]
        x2 = x.reshape(n, -1)
        sign = jnp.ones_like(x2).at[jnp.arange(n), labels].set(-1.0)
        margins = jnp.maximum(0.0, 1.0 + sign * x2)
        p = self.lp.hinge_loss_param
        if p and str(p.norm).upper() == "L2":
            return [jnp.sum(jnp.square(margins)) / n], state
        return [jnp.sum(margins) / n], state


@register("MultinomialLogisticLoss")
class MultinomialLogisticLossLayer(LossBase):
    """NLL on already-normalized probabilities
    (multinomial_logistic_loss_layer.cpp)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        prob = self.f(bottoms[0]).astype(jnp.float32)
        labels = bottoms[1].astype(jnp.int32).reshape(-1)
        n = prob.shape[0]
        picked = prob.reshape(n, -1)[jnp.arange(n), labels]
        loss = -jnp.sum(jnp.log(jnp.maximum(picked, 1e-20))) / n
        return [loss], state


@register("InfogainLoss")
class InfogainLossLayer(LossBase):
    """NLL weighted by an infogain matrix H (infogain_loss_layer.cpp).
    H comes from bottom[2] or from a file (not yet supported)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        self.H_file = None
        if len(in_shapes) < 3:
            p = self.lp.infogain_loss_param
            if not (p and p.source):
                raise ValueError(f"{self.name}: infogain needs H as third "
                                 "bottom or a source file")
            import os
            from ..io import load_blob_binaryproto
            k = in_shapes[0][1]
            src = os.path.join(getattr(self, "model_dir", ""), p.source)
            self.H_file = jnp.asarray(
                load_blob_binaryproto(src).reshape(k, k), jnp.float32)
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        prob = self.f(bottoms[0]).astype(jnp.float32)
        labels = bottoms[1].astype(jnp.int32).reshape(-1)
        if self.H_file is not None:
            H = self.H_file
        else:
            H = self.f(bottoms[2]).astype(jnp.float32).reshape(
                prob.shape[1], prob.shape[1])
        n = prob.shape[0]
        rows = H[labels]  # (n, K)
        loss = -jnp.sum(rows * jnp.log(jnp.maximum(prob.reshape(n, -1), 1e-20))) / n
        return [loss], state


@register("ContrastiveLoss")
class ContrastiveLossLayer(LossBase):
    """Siamese-pair loss (contrastive_loss_layer.cpp):
    y=1 similar -> d^2; y=0 dissimilar -> max(margin-d, 0)^2 (or the legacy
    margin-d^2 variant)."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        return [()]

    def apply(self, params, state, bottoms, *, train, rng):
        a = self.f(bottoms[0]).astype(jnp.float32)
        b = self.f(bottoms[1]).astype(jnp.float32)
        y = bottoms[2].astype(jnp.float32).reshape(-1)
        p = self.lp.contrastive_loss_param
        margin = p.margin if p else 1.0
        legacy = bool(p and p.legacy_version)
        d2 = jnp.sum(jnp.square(a - b), axis=1)
        if legacy:
            dissim = jnp.maximum(margin - d2, 0.0)
        else:
            dissim = jnp.square(jnp.maximum(margin - jnp.sqrt(d2 + 1e-12), 0.0))
        per = y * d2 + (1.0 - y) * dissim
        return [jnp.sum(per) / (2.0 * a.shape[0])], state


@register("Accuracy")
class AccuracyLayer(Layer):
    """Top-k accuracy metric (accuracy_layer.cpp). Not a loss (weight 0);
    optional second top = per-class accuracy."""

    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.accuracy_param
        self.top_k = p.top_k if p else 1
        self.axis = (p.axis if p else 1) % len(in_shapes[0])
        self.ignore = p.ignore_label if (p and p.has("ignore_label")) else None
        tops = [()]
        if len(self.lp.top) > 1:
            tops.append((in_shapes[0][self.axis],))
        return tops

    def apply(self, params, state, bottoms, *, train, rng):
        scores = self.f(bottoms[0]).astype(jnp.float32)
        labels = bottoms[1].astype(jnp.int32)
        s_last = jnp.moveaxis(scores, self.axis, -1)
        labels_flat = labels.reshape(s_last.shape[:-1])
        # rank of the true class: count of classes scoring strictly higher
        true_score = jnp.take_along_axis(s_last, labels_flat[..., None], axis=-1)
        higher = jnp.sum(s_last > true_score, axis=-1)
        correct = (higher < self.top_k).astype(jnp.float32)
        if self.ignore is not None:
            mask = labels_flat != self.ignore
            correct = jnp.where(mask, correct, 0.0)
            denom = jnp.maximum(jnp.sum(mask), 1)
        else:
            denom = correct.size
        acc = jnp.sum(correct) / denom
        tops = [acc]
        if len(self.lp.top) > 1:
            k = s_last.shape[-1]
            onehot = jax.nn.one_hot(labels_flat, k)
            per_class_correct = jnp.sum(onehot * correct[..., None],
                                        axis=tuple(range(onehot.ndim - 1)))
            per_class_count = jnp.maximum(
                jnp.sum(onehot, axis=tuple(range(onehot.ndim - 1))), 1.0)
            tops.append(per_class_correct / per_class_count)
        return tops, state
