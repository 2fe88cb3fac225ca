"""Plain float32 reference forward for the benchmark's CNN configurations.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: it parses the prototxt text itself and computes
each layer in plain `jax.numpy`/`lax` float32 at `highest` matmul precision,
following the published Caffe layer definitions (Jia et al. 2014 and the
BVLC layer catalogue; NVCaffe's fused scale/bias in BatchNorm). It interprets
only the layer types `models/alexnet` and `models/resnet50` use; any other
type is an error, not a guess.

Departures from the published arithmetic: none. Dropout is the identity in
the TEST phase and refused in TRAIN (its mask is the program's own random
stream), which is why AlexNet is compared in TEST. BatchNorm in TRAIN uses
the batch's own biased statistics, so ResNet-50 is compared in TRAIN.

Weights are the system's own freshly initialised arrays in Caffe's blob
layouts: convolution `[out, in/group, kh, kw]`, inner product `[out, in]`.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_TOKEN = re.compile(r'"(?:[^"\\]|\\.)*"|[{}:]|[^\s{}:"#]+|#[^\n]*')


def parse_prototxt(text: str) -> dict:
    """Protobuf text format -> nested dicts; every field is a list, since
    the format itself does not say which fields repeat."""
    tokens = [t for t in _TOKEN.findall(text) if not t.startswith("#")]
    pos = 0

    def scalar(tok: str):
        if tok.startswith('"'):
            return tok[1:-1]
        if tok in ("true", "false"):
            return tok == "true"
        try:
            return int(tok)
        except ValueError:
            try:
                return float(tok)
            except ValueError:
                return tok  # an enum name

    def message(closing: str | None) -> dict:
        nonlocal pos
        out: dict = {}
        while pos < len(tokens):
            key = tokens[pos]
            pos += 1
            if key == closing:
                return out
            if tokens[pos] == ":":
                pos += 1
            if tokens[pos] == "{":
                pos += 1
                value = message("}")
            else:
                value = scalar(tokens[pos])
                pos += 1
            out.setdefault(key, []).append(value)
        if closing is not None:
            raise ValueError("prototxt: unbalanced braces")
        return out

    return message(None)


def _one(msg: dict, key: str, default=None):
    return msg[key][0] if key in msg else default


def _pair(msg: dict, key: str, default: int) -> tuple[int, int]:
    """Caffe's `key` / `key_h` + `key_w` spelling of a 2-D size."""
    if f"{key}_h" in msg or f"{key}_w" in msg:
        return _one(msg, f"{key}_h", default), _one(msg, f"{key}_w", default)
    vals = msg.get(key, [default])
    return (vals[0], vals[-1]) if len(vals) <= 2 else tuple(vals[:2])


def _kernel(p: dict) -> tuple[int, int]:
    return _pair(p, "kernel" if "kernel_h" in p else "kernel_size", 0)


def layers_for_phase(net: dict, phase: str) -> list[dict]:
    """The net's layers after Caffe's include/exclude phase rules."""
    out = []
    for layer in net.get("layer", []):
        inc = [_one(r, "phase") for r in layer.get("include", [])]
        exc = [_one(r, "phase") for r in layer.get("exclude", [])]
        if inc and phase not in inc:
            continue
        if phase in exc:
            continue
        out.append(layer)
    return out


def input_tops(net: dict, phase: str) -> dict[str, list[int]]:
    """{top: dims} of the net's Input layers, as declared."""
    tops = {}
    for layer in layers_for_phase(net, phase):
        if _one(layer, "type") != "Input":
            continue
        shapes = [s["dim"] for s in _one(layer, "input_param")["shape"]]
        if len(shapes) == 1:
            shapes = shapes * len(layer["top"])
        tops.update(zip(layer["top"], shapes))
    return tops


def set_input_dims(net: dict, batch: int, hw=None) -> None:
    """Rewrite the Input layers' batch (and image size) in place."""
    for layer in net.get("layer", []):
        if _one(layer, "type") != "Input":
            continue
        for shape in _one(layer, "input_param")["shape"]:
            shape["dim"][0] = batch
            if hw is not None and len(shape["dim"]) == 4:
                shape["dim"][2:] = list(hw)


def logits_blob(net: dict, phase: str) -> str:
    """The blob the classification loss reads: SoftmaxWithLoss's first
    bottom in TRAIN, Accuracy's in TEST."""
    want = "SoftmaxWithLoss" if phase == "TRAIN" else "Accuracy"
    for layer in layers_for_phase(net, phase):
        if _one(layer, "type") == want:
            return layer["bottom"][0]
    raise ValueError(f"no {want} layer in the {phase} net")


# -- layers -----------------------------------------------------------------

def _convolution(p: dict, x, w, b):
    kh, kw = _kernel(p)
    sh, sw = _pair(p, "stride", 1)
    ph, pw = _pair(p, "pad", 0)
    if tuple(w.shape[2:]) != (kh, kw):
        raise ValueError(f"conv weight {w.shape} vs kernel {(kh, kw)}")
    y = lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=_one(p, "group", 1),
        precision=lax.Precision.HIGHEST)
    return y if b is None else y + b.reshape(1, -1, 1, 1)


def _pool_out(size: int, k: int, pad: int, stride: int) -> int:
    """Caffe rounds the pooled size up and then drops a last window that
    would start in the padding (pooling_layer.cpp)."""
    out = math.ceil((size + 2 * pad - k) / stride) + 1
    if pad and (out - 1) * stride >= size + pad:
        out -= 1
    return out


def _pooling(p: dict, x):
    n, c, h, w = x.shape
    method = _one(p, "pool", "MAX")
    if _one(p, "global_pooling", False):
        kh, kw, sh, sw, ph, pw = h, w, 1, 1, 0, 0
    else:
        kh, kw = _kernel(p)
        sh, sw = _pair(p, "stride", 1)
        ph, pw = _pair(p, "pad", 0)
    oh, ow = _pool_out(h, kh, ph, sh), _pool_out(w, kw, pw, sw)
    # pad the high side far enough for the last (clipped) window
    hi_h = (oh - 1) * sh + kh - h - ph
    hi_w = (ow - 1) * sw + kw - w - pw
    pads = ((0, 0), (0, 0), (ph, max(hi_h, 0)), (pw, max(hi_w, 0)))
    dims, strides = (1, 1, kh, kw), (1, 1, sh, sw)
    if method == "MAX":
        xp = jnp.pad(x, pads, constant_values=-jnp.inf)
        return lax.reduce_window(xp, -jnp.inf, lax.max, dims, strides,
                                 "VALID")[:, :, :oh, :ow]
    if method != "AVE":
        raise ValueError(f"pooling method {method!r} not in the reference")
    xp = jnp.pad(x, pads)
    total = lax.reduce_window(xp, 0.0, lax.add, dims, strides,
                              "VALID")[:, :, :oh, :ow]

    def extent(size, k, pad, stride, out):
        # Caffe's AVE divisor counts padding cells but clips the window to
        # size + pad (pooling_layer.cpp)
        start = np.arange(out) * stride - pad
        end = np.minimum(start + k, size + pad)
        return (end - start).astype(np.float32)
    div = np.outer(extent(h, kh, ph, sh, oh), extent(w, kw, pw, sw, ow))
    return total / jnp.asarray(div)[None, None]


def _lrn(p: dict, x):
    if _one(p, "norm_region", "ACROSS_CHANNELS") != "ACROSS_CHANNELS":
        raise ValueError("within-channel LRN is not in the reference")
    n = _one(p, "local_size", 5)
    alpha, beta = _one(p, "alpha", 1.0), _one(p, "beta", 0.75)
    k = _one(p, "k", 1.0)
    half = (n - 1) // 2
    sq = jnp.pad(jnp.square(x), ((0, 0), (half, half), (0, 0), (0, 0)))
    c = x.shape[1]
    window = sum(sq[:, i:i + c] for i in range(n))
    return x * jnp.power(k + (alpha / n) * window, -beta)


def _batch_norm(p: dict, x, params: dict, state: dict, train: bool):
    # NVCaffe/cuDNN floor the epsilon at 1e-5 (CUDNN_BN_MIN_EPSILON)
    eps = max(_one(p, "eps", 1e-5), 1e-5)
    use_global = _one(p, "use_global_stats", not train)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if use_global:
        mean, var = state["mean"], state["var"]
    else:
        axes = tuple(i for i in range(x.ndim) if i != 1)
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(jnp.square(x - mean.reshape(shape)), axis=axes)
    y = (x - mean.reshape(shape)) / jnp.sqrt(var.reshape(shape) + eps)
    if "scale" in params:
        y = y * params["scale"].reshape(shape) + params["bias"].reshape(shape)
    return y


def forward(net: dict, phase: str, params: dict, state: dict,
            feeds: dict) -> dict:
    """All blobs of the net in `phase`, float32, from `feeds` (the Input
    layers' tops). Loss and accuracy layers are not evaluated."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    params, state = f32(params), f32(state)
    train = phase == "TRAIN"
    env: dict = {}
    with jax.default_matmul_precision("highest"):
        for layer in layers_for_phase(net, phase):
            kind, name = _one(layer, "type"), _one(layer, "name")
            if kind == "Input":
                for top in layer["top"]:
                    v = jnp.asarray(feeds[top])
                    env[top] = (v.astype(jnp.float32)
                                if jnp.issubdtype(v.dtype, jnp.floating)
                                else v)
                continue
            if kind in ("SoftmaxWithLoss", "Accuracy"):
                continue
            bottoms = [env[b] for b in layer["bottom"]]
            lp = params.get(name, {})
            if kind == "Convolution":
                y = _convolution(_one(layer, "convolution_param"),
                                 bottoms[0], lp["weight"], lp.get("bias"))
            elif kind == "InnerProduct":
                x = bottoms[0].reshape(bottoms[0].shape[0], -1)
                y = jnp.matmul(x, lp["weight"].T,
                               precision=lax.Precision.HIGHEST)
                if "bias" in lp:
                    y = y + lp["bias"]
            elif kind == "ReLU":
                slope = _one(_one(layer, "relu_param", {}),
                             "negative_slope", 0.0)
                y = jnp.where(bottoms[0] > 0, bottoms[0],
                              slope * bottoms[0])
            elif kind == "Pooling":
                y = _pooling(_one(layer, "pooling_param"), bottoms[0])
            elif kind == "LRN":
                y = _lrn(_one(layer, "lrn_param", {}), bottoms[0])
            elif kind == "BatchNorm":
                y = _batch_norm(_one(layer, "batch_norm_param", {}),
                                bottoms[0], lp, state.get(name, {}), train)
            elif kind == "Eltwise":
                ep = _one(layer, "eltwise_param", {})
                if _one(ep, "operation", "SUM") != "SUM" or "coeff" in ep:
                    raise ValueError("only plain SUM Eltwise is in the "
                                     "reference")
                y = sum(bottoms[1:], bottoms[0])
            elif kind == "Dropout":
                if train:
                    raise ValueError(
                        "Dropout in TRAIN draws the program's own mask; "
                        "compare this net in TEST")
                y = bottoms[0]
            else:
                raise ValueError(f"layer type {kind!r} is not in the "
                                 "reference")
            env[layer["top"][0]] = y
    return env


def macs_per_sample(net: dict, phase: str = "TRAIN") -> int:
    """Forward multiply-accumulates of one sample: convolution and inner
    product terms only (what the MXU does; pooling, normalisation and
    activations move bytes, not MACs). Shapes come from the prototxt by
    this file's own layer arithmetic."""
    shapes = dict(input_tops(net, phase))
    total = 0
    for layer in layers_for_phase(net, phase):
        kind = _one(layer, "type")
        if kind in ("Input", "SoftmaxWithLoss", "Accuracy"):
            continue
        n, *rest = shapes[layer["bottom"][0]]
        out = [n, *rest]
        if kind == "Convolution":
            p = _one(layer, "convolution_param")
            c, h, w = rest
            kh, kw = _kernel(p)
            sh, sw = _pair(p, "stride", 1)
            ph, pw = _pair(p, "pad", 0)
            o, g = _one(p, "num_output"), _one(p, "group", 1)
            oh = (h + 2 * ph - kh) // sh + 1
            ow = (w + 2 * pw - kw) // sw + 1
            total += o * (c // g) * kh * kw * oh * ow
            out = [n, o, oh, ow]
        elif kind == "InnerProduct":
            o = _one(_one(layer, "inner_product_param"), "num_output")
            total += o * math.prod(rest)
            out = [n, o]
        elif kind == "Pooling":
            p = _one(layer, "pooling_param")
            c, h, w = rest
            if _one(p, "global_pooling", False):
                out = [n, c, 1, 1]
            else:
                kh, kw = _kernel(p)
                sh, sw = _pair(p, "stride", 1)
                ph, pw = _pair(p, "pad", 0)
                out = [n, c, _pool_out(h, kh, ph, sh),
                       _pool_out(w, kw, pw, sw)]
        shapes[layer["top"][0]] = out
    return total
