"""Typed configuration schema — the Caffe parameter surface, in dataclasses.

Mirrors the *semantics* of the reference's protobuf schema
(/root/reference/src/caffe/proto/caffe.proto, 1,573 lines): NetParameter,
LayerParameter (with per-op sub-messages), SolverParameter, fillers, net-state
rules, precision/dtype fields. The reference compiles this schema with protoc;
here each message is a dataclass coerced from the untyped text-format tree
(`text_format.PbNode`), which keeps the whole config layer importable Python
with no codegen while reading the reference's own prototxt files.

Only fields the TPU framework interprets are declared; unknown fields parse
fine (they stay in the PbNode) and are reported by `Message.unknown_fields`
rather than crashing, mirroring proto2's tolerant-reader behavior.
"""

from __future__ import annotations

import dataclasses
import types
import typing
from dataclasses import dataclass, field as dc_field
from typing import Any, get_args, get_origin

from ..utils import spans
from .text_format import PbEnum, PbNode, parse


# ---------------------------------------------------------------------------
# Coercion machinery
# ---------------------------------------------------------------------------

def _coerce_scalar(value: Any, target: type) -> Any:
    if target is float:
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            return float(value)
    elif target is int:
        if isinstance(value, bool):
            raise TypeError("bool where int expected")
        if isinstance(value, int):
            return value
        if isinstance(value, float) and value.is_integer():
            return int(value)
    elif target is bool:
        if isinstance(value, bool):
            return value
        if isinstance(value, PbEnum) and value in ("true", "false"):
            return value == "true"
        if isinstance(value, int) and value in (0, 1):
            return bool(value)
    elif target is str:
        if isinstance(value, str):
            return str(value)
    raise TypeError(f"cannot coerce {value!r} to {target.__name__}")


_SCHEMA_CACHE: dict[type, tuple] = {}

# string-typed fields that are protobuf enums (printed unquoted)
_ENUM_FIELD_NAMES = {
    "pool", "operation", "norm_region", "backend", "phase", "variance_norm",
    "norm", "round_mode", "engine", "solver_mode", "snapshot_format",
    "regularization_type", "share_mode", "gridbox_type", "coverage_type",
    "crop_mode", "forward_type", "backward_type", "forward_math",
    "backward_math", "default_forward_type", "default_backward_type",
    "default_forward_math", "default_backward_math", "solver_data_type",
}


@dataclass
class Message:
    """Base for all schema messages; subclasses are plain dataclasses."""

    @classmethod
    def _schema(cls):
        """Per-class (fields, resolved hints, name->field map) cache —
        from_node runs once per node in a net with hundreds of layers,
        so hint resolution must not."""
        cached = _SCHEMA_CACHE.get(cls)
        if cached is None:
            fields = dataclasses.fields(cls)
            cached = (fields, typing.get_type_hints(cls),
                      {f.name: f for f in fields
                       if not f.name.startswith("_")})
            _SCHEMA_CACHE[cls] = cached
        return cached

    @classmethod
    def from_node(cls, node: PbNode):
        _fields, hints, field_map = cls._schema()
        kwargs: dict[str, Any] = {}
        known = field_map.keys()
        # iterate the fields PRESENT in the node (a layer sets a
        # handful) rather than the full schema (LayerParameter declares
        # ~60) — the prototxt-load hot path for big nets
        for name, vals in node.fields.items():
            f = field_map.get(name)
            if f is None or not vals:
                continue
            target = hints[f.name]
            origin = get_origin(target)
            if origin is typing.Union or origin is types.UnionType:
                non_none = [a for a in get_args(target) if a is not type(None)]
                target = non_none[0]
                origin = get_origin(target)
            try:
                if origin in (list, tuple):
                    (elem,) = get_args(target)[:1]
                    kwargs[f.name] = [_coerce_value(v, elem, f.name) for v in vals]
                else:
                    kwargs[f.name] = _coerce_value(vals[-1], target, f.name)
            except TypeError as e:
                raise TypeError(f"{cls.__name__}.{f.name}: {e}") from e
        obj = cls(**kwargs)
        obj._node = node
        return obj

    @classmethod
    def from_text(cls, text: str):
        with spans.phase("parse", message=cls.__name__, bytes=len(text)):
            return cls.from_node(parse(text))

    @classmethod
    def from_file(cls, path: str):
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_text(f.read())

    @property
    def unknown_fields(self) -> list[str]:
        """Fields present in the source text but absent from the
        schema. Computed lazily (and cached as `_unknown`) — the eager
        per-node set difference was measurable across a 370-layer
        net's ~9k message nodes, and almost nothing reads this."""
        cached = getattr(self, "_unknown", None)
        if cached is None:
            node = getattr(self, "_node", None)
            if node is None:
                return []
            _f, _h, field_map = type(self)._schema()
            cached = sorted(set(node.keys()) - field_map.keys())
            self._unknown = cached
        return cached

    def to_node(self) -> PbNode:
        """Serialize back to a text-format tree. Emits only fields that
        differ from their defaults (proto2 printer behavior); enum-valued
        string fields print unquoted."""
        fields, hints, _field_map = type(self)._schema()
        node = PbNode()
        for f in fields:
            if f.name.startswith("_"):
                continue
            value = getattr(self, f.name)
            default = (f.default_factory() if f.default_factory
                       is not dataclasses.MISSING else f.default)
            if value is None or value == default and not self.has(f.name):
                continue
            vals = value if isinstance(value, list) else [value]
            if not vals and isinstance(value, list):
                continue
            for v in vals:
                if isinstance(v, Message):
                    node.add(f.name, v.to_node())
                elif f.name in _ENUM_FIELD_NAMES and isinstance(v, str):
                    node.add(f.name, PbEnum(v))
                else:
                    node.add(f.name, v)
        return node

    def to_prototxt(self) -> str:
        return self.to_node().to_text()

    def has(self, name: str) -> bool:
        """proto2-style presence test: was the field set in the source text?"""
        node = getattr(self, "_node", None)
        return node is not None and name in node

    def clear(self, name: str) -> None:
        """proto2-style ClearField: reset the field to its schema
        default and drop source-text presence, so `has(name)` becomes
        False. The CLI uses this to let a flag override a prototxt
        value's PRESENCE, not just its value (e.g. -grad_bucket_mb
        switching a recipe off its reduce_buckets sizing mode)."""
        node = getattr(self, "_node", None)
        if node is not None:
            node.fields.pop(name, None)
        for f in dataclasses.fields(self):
            if f.name == name:
                setattr(self, name,
                        f.default_factory() if f.default_factory
                        is not dataclasses.MISSING else f.default)
                return
        raise AttributeError(f"{type(self).__name__} has no field {name!r}")


def _coerce_value(value: Any, target: Any, fname: str) -> Any:
    if isinstance(target, type) and issubclass(target, Message):
        if not isinstance(value, PbNode):
            raise TypeError(f"expected message for {fname}, got {value!r}")
        return target.from_node(value)
    if target is Any:
        return value
    if isinstance(value, PbNode):
        raise TypeError(f"unexpected message value for scalar field {fname}")
    return _coerce_scalar(value, target)


def _rep() -> Any:
    return dc_field(default_factory=list)


# ---------------------------------------------------------------------------
# Fillers  (reference: caffe.proto FillerParameter; src/caffe/filler.hpp)
# ---------------------------------------------------------------------------

@dataclass
class FillerParameter(Message):
    # beside the reference's types, two TPU-native ones, a state-space
    # mixer's starts: "log_arange" fills ln(i + 1) along the last axis;
    # "softplus_inverse_log_uniform" fills v with softplus(v) log-uniform
    # in [min, max], floored at `value`
    type: str = "constant"
    value: float = 0.0
    min: float = 0.0
    max: float = 1.0
    mean: float = 0.0
    std: float = 1.0
    sparse: int = -1
    # xavier/msra normalization choice: FAN_IN / FAN_OUT / AVERAGE
    variance_norm: str = "FAN_IN"
    # TPU-native extension: fill the first 1/tile of the last axis and
    # repeat it, so that entries j and j + last/tile start equal (a router
    # whose experts come in tile groups with equal columns)
    tile: int = 1
    # TPU-native extension: fill the first half of the last axis and make
    # the second half its negative, so that entries j and j + last/2 start
    # opposite (a router whose experts e and e + E/2 score a token -x and
    # x: by symmetry each half of the experts is chosen by half the tokens)
    mirror: bool = False


# ---------------------------------------------------------------------------
# Shapes and per-param config
# ---------------------------------------------------------------------------

@dataclass
class BlobShape(Message):
    dim: list[int] = _rep()


@dataclass
class ParamSpec(Message):
    """Per-learnable-param training config (caffe.proto ParamSpec):
    shared-weight naming, lr/decay multipliers."""
    name: str = ""
    lr_mult: float = 1.0
    decay_mult: float = 1.0
    # share_mode STRICT/PERMISSIVE accepted but sharing always requires
    # identical shapes in this framework
    share_mode: str = "STRICT"


@dataclass
class NetStateRule(Message):
    """Phase/level/stage inclusion rule (caffe.proto NetStateRule;
    evaluated in reference net.cpp:435-498)."""
    phase: str = ""
    min_level: int = -(2**31)
    max_level: int = 2**31 - 1
    stage: list[str] = _rep()
    not_stage: list[str] = _rep()


@dataclass
class NetState(Message):
    phase: str = "TEST"
    level: int = 0
    stage: list[str] = _rep()


# ---------------------------------------------------------------------------
# Op parameter sub-messages
# ---------------------------------------------------------------------------

@dataclass
class ConvolutionParameter(Message):
    num_output: int = 0
    bias_term: bool = True
    pad: list[int] = _rep()
    kernel_size: list[int] = _rep()
    stride: list[int] = _rep()
    dilation: list[int] = _rep()
    pad_h: int = 0
    pad_w: int = 0
    kernel_h: int = 0
    kernel_w: int = 0
    stride_h: int = 0
    stride_w: int = 0
    group: int = 1
    weight_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None
    axis: int = 1
    force_nd_im2col: bool = False
    # engine CAFFE/CUDNN accepted and ignored: XLA picks conv algorithms,
    # replacing the reference's cuDNN algo auto-seek
    # (reference cudnn_conv_layer.cpp).
    engine: str = "DEFAULT"
    cudnn_math_override: int = -1


@dataclass
class PoolingParameter(Message):
    pool: str = "MAX"  # MAX / AVE / STOCHASTIC
    pad: int = 0
    pad_h: int = 0
    pad_w: int = 0
    kernel_size: int = 0
    kernel_h: int = 0
    kernel_w: int = 0
    stride: int = 1
    stride_h: int = 0
    stride_w: int = 0
    global_pooling: bool = False
    engine: str = "DEFAULT"
    # reference rounds output size UP (ceil) — see pooling_layer.cpp
    round_mode: str = "CEIL"


@dataclass
class InnerProductParameter(Message):
    num_output: int = 0
    bias_term: bool = True
    weight_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None
    axis: int = 1
    transpose: bool = False


@dataclass
class ReLUParameter(Message):
    negative_slope: float = 0.0
    engine: str = "DEFAULT"


@dataclass
class PReLUParameter(Message):
    filler: FillerParameter | None = None
    channel_shared: bool = False


@dataclass
class ELUParameter(Message):
    alpha: float = 1.0


@dataclass
class SigmoidParameter(Message):
    engine: str = "DEFAULT"


@dataclass
class TanHParameter(Message):
    engine: str = "DEFAULT"


@dataclass
class PowerParameter(Message):
    power: float = 1.0
    scale: float = 1.0
    shift: float = 0.0


@dataclass
class ExpParameter(Message):
    base: float = -1.0
    scale: float = 1.0
    shift: float = 0.0


@dataclass
class LogParameter(Message):
    base: float = -1.0
    scale: float = 1.0
    shift: float = 0.0


@dataclass
class ThresholdParameter(Message):
    threshold: float = 0.0


@dataclass
class DropoutParameter(Message):
    dropout_ratio: float = 0.5
    engine: str = "DEFAULT"


@dataclass
class LRNParameter(Message):
    local_size: int = 5
    alpha: float = 1.0
    beta: float = 0.75
    norm_region: str = "ACROSS_CHANNELS"
    k: float = 1.0
    engine: str = "DEFAULT"


@dataclass
class BatchNormParameter(Message):
    use_global_stats: bool = False  # presence matters; see has("use_global_stats")
    moving_average_fraction: float = 0.999
    eps: float = 1e-5
    # NVCaffe extension: fused scale+bias inside BN
    scale_bias: bool = False
    scale_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None


@dataclass
class ScaleParameter(Message):
    axis: int = 1
    num_axes: int = 1
    filler: FillerParameter | None = None
    bias_term: bool = False
    bias_filler: FillerParameter | None = None


@dataclass
class BiasParameter(Message):
    axis: int = 1
    num_axes: int = 1
    filler: FillerParameter | None = None


@dataclass
class MVNParameter(Message):
    normalize_variance: bool = True
    across_channels: bool = False
    eps: float = 1e-9


@dataclass
class SoftmaxParameter(Message):
    axis: int = 1
    engine: str = "DEFAULT"


@dataclass
class LossParameter(Message):
    ignore_label: int | None = None
    normalization: str = "VALID"  # FULL / VALID / BATCH_SIZE / NONE
    normalize: bool = True  # legacy pre-normalization flag


@dataclass
class AccuracyParameter(Message):
    top_k: int = 1
    axis: int = 1
    ignore_label: int | None = None


@dataclass
class AttentionParameter(Message):
    """TPU-native extension (no reference analogue — SURVEY §5.7: the
    reference has no attention op at all): multi-head self-attention over
    (N, S, C) blobs, with optional Pallas flash kernels and ring-attention
    sequence parallelism."""
    num_heads: int = 1
    causal: bool = False
    use_flash: bool = False
    # route through ring attention with the sequence dim sharded over the
    # mesh 'model' axis (ops/attention.py sequence_parallel_attention).
    # Takes effect when the solver runs with a mesh whose model axis > 1;
    # single-device execution falls back to standard attention.
    sequence_parallel: bool = False
    bias_term: bool = True
    weight_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None
    # grouped key/value heads: query head n reads key/value head
    # n // (num_heads / num_kv_heads). 0 = as many as query heads
    num_kv_heads: int = 0
    # head size; 0 = channels / num_heads. When set, the projections are
    # ((num_heads + 2 num_kv_heads) * head_dim, C) and (C, num_heads *
    # head_dim), which need not be square
    head_dim: int = 0
    # sliding window: key j is visible to query i iff i - window < j <= i
    # (with causal). 0 = no window
    window: int = 0
    # rotary position embedding over the whole head (rotate-half
    # convention), positions 0..S-1 in each sequence (i mod S/2 under
    # block_diffusion). 0 = no positions
    rope_theta: float = 0.0
    # latent attention (kv_lora_rank > 0; arXiv:2405.04434): queries and
    # keys/values come through low-rank projections with an RMSNorm
    # between the two factors, a head is qk_nope_head_dim lanes without
    # positions plus qk_rope_head_dim rotary ones, the rotary key is ONE
    # head that every query head shares, and values are v_head_dim wide.
    # Blobs: q_a_weight (q_lora_rank, C), q_norm (q_lora_rank),
    # q_b_weight (heads * (nope + rope), q_lora_rank), kv_a_weight
    # (kv_lora_rank + rope, C), kv_norm (kv_lora_rank), kv_b_weight
    # (heads * (nope + v), kv_lora_rank; a head's rows are its key rows,
    # then its value rows), proj_weight (C, heads * v). num_kv_heads,
    # head_dim, window and sequence_parallel have no meaning here
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the rotary lanes turn in adjacent pairs (x_2i, x_2i+1) instead of
    # rotate-half; latent attention only
    rope_interleave: bool = False
    # eps of the two RMSNorms inside the latent path, and of qk_norm's
    norm_eps: float = 1e-6
    # block diffusion (arXiv:2503.09573), the block length; 0 = off. The
    # bottom is one [noisy | clean] sequence of two halves of S / 2 (the
    # BlockDiffusionNoise layer's first top, embedded): row i sits at
    # position i mod S/2 in block (i mod S/2) // block_diffusion, a noisy
    # row sees its own block among the noisy and the blocks before it
    # among the clean, a clean row the clean blocks up to its own. The
    # mask and the positions go together; neither causal nor window, and
    # not the latent or sequence_parallel paths
    block_diffusion: int = 0
    # an RMSNorm with a learnable scale of head_dim on each query and each
    # key head, before the rotary turn: blobs q_norm and k_norm (head_dim,),
    # filled with ones. Not in the latent path, which has norms of its own
    qk_norm: bool = False
    # compressed convolutional attention (arXiv:2510.04476), the third form
    # of the layer: attention inside a latent of num_heads query and
    # num_kv_heads key/value heads of head_dim, narrower than the channels.
    # With shift(x)_t = x_{t-1} (zero at t = 0): queries and keys are
    # projected down (q_weight (H d, C), k_weight (G d, C)), mixed along the
    # sequence over z = [q | k] seen as H + G heads of d by a causal
    # convolution a channel (conv0_weight ((H + G) d, cca_time0), conv0_bias;
    # the LAST tap reads the current position) and then one a head
    # (conv1_weight ((H + G) d, d, cca_time1): [out, in of the head, tap],
    # conv1_bias), summed with the mean of each other taken before the
    # convolutions (query head i: (q_i + k_j) / 2, j its group; key head j:
    # (mean of its group's q_i + k_j) / 2), normalised to length sqrt(d)
    # (keys times the learned temperature `temp` (G,), ones), and turned by
    # rotary positions over the first rotary_fraction of each head
    # (rotate-half inside those lanes), the rest untouched. The first half
    # of the value lanes read the current token (v1_weight (G d / 2, C)),
    # the second half the previous one (v2_weight). proj_weight (C, H d)
    # goes back up. Needs causal, head_dim, rope_theta and bias_term:
    # false; neither window, block_diffusion, qk_norm nor the latent or
    # sequence_parallel paths
    cca: bool = False
    cca_time0: int = 2
    cca_time1: int = 2
    rotary_fraction: float = 1.0


@dataclass
class ParameterParameter(Message):
    """parameter_layer.hpp: expose a learnable blob of the given shape."""
    shape: BlobShape | None = None


@dataclass
class LayerNormParameter(Message):
    """TPU-native extension (the reference has BatchNorm/MVN but no
    per-position LayerNorm — it predates transformers): normalize over the
    trailing axis with learnable scale/bias."""
    eps: float = 1e-5
    scale_bias: bool = True


@dataclass
class RMSNormParameter(Message):
    """TPU-native extension: x / sqrt(mean(x^2) + eps) over the trailing
    axis, statistics in float32, times a learnable scale (no bias)."""
    eps: float = 1e-6


@dataclass
class MoEParameter(Message):
    """TPU-native extension (no reference analogue — SURVEY §2.7: EP
    absent): mixture-of-experts FFN with top-k routing and capacity,
    experts shardable over a mesh axis (ops/moe.py). A second top, when
    named, carries the load-balancing auxiliary loss."""
    num_experts: int = 0
    hidden_dim: int = 0
    top_k: int = 1
    capacity_factor: float = 2.0
    weight_filler: FillerParameter | None = None
    # no capacity and no dropped token: the top_k largest router logits,
    # softmax over those, rows sorted by expert, grouped matrix products
    # over experts of unbiased matrices: gated units of three, (act(x w1)
    # * (x w3)) w2, or with `gated: false` ungated ones of two, act(x w1)
    # w2 (ops/moe.py moe_dropless). A second bottom, when given,
    # is what the router scores; the second top is the rows each held
    # expert received
    dropless: bool = False
    # the share of an expert-parallel deployment this layer holds: experts
    # first_expert .. first_expert + experts_held - 1 of num_experts. The
    # router keeps num_experts outputs; the result is the held experts'
    # part. 0 = all of them
    experts_held: int = 0
    first_expert: int = 0
    # a share's sorted buffer holds this many times the held experts'
    # average share of the (token, choice) pairs, in whole row tiles; the
    # device takes the buffer of every pair whenever the live rows reach it
    # (ops/moe.py _row_bound, scope `moe.fallback`): a router's skew costs
    # time, never a row. A debt, not a knob to tune a recipe: it exists for
    # a frozen fresh router whose selection bias nothing updates, and goes
    # when that update rule lands (ROADMAP Speed 13)
    row_bound: float = 1.5
    # the rest is the dropless path's. scoring "softmax": the top_k largest
    # logits, softmax over those. "sigmoid" (arXiv:2412.19437): s =
    # sigmoid(logits); the top_k largest of s + select_bias (a blob after
    # `gate`, one value an expert; it selects and does not weigh); weights
    # routed_scaling_factor * s / sum of the chosen s.
    # "softmax_all": p = softmax(logits) over every expert; the top_k
    # largest of p + select_bias; weights routed_scaling_factor * p of the
    # chosen, not renormalised (at top_k 1 the chosen expert's own
    # probability, where "softmax" gives 1)
    scoring: str = "softmax"
    # filler of select_bias (default: zeros), and of the router matrix
    # `gate` (default: gaussian 0.02)
    bias_filler: FillerParameter | None = None
    gate_filler: FillerParameter | None = None
    routed_scaling_factor: float = 1.0
    # the activation in an expert, act(x w1): relu | silu | relu2 (relu
    # squared)
    activation: str = "relu"
    # whether an expert has a gate matrix: (act(x w1) * (x w3)) w2, or
    # without it act(x w1) w2 and no `w3` (nor `shared_w3`) blob
    gated: bool = True
    # what the second bottom is. "input": a tensor like the first, which
    # the router matrix `gate` multiplies. "logits": the router's logits
    # themselves, (..., num_experts), from layers of the net's own (a
    # router that is a network); no `gate` blob is declared
    router: str = "input"
    # shared experts: one unit of the experts' form (gated or not) of
    # shared_experts * hidden_dim that every token passes through, added to the routed experts' part (blobs
    # shared_w1, shared_w3 (C, n h), shared_w2 (n h, C)). Under expert
    # parallelism every chip computes it for its own tokens
    shared_experts: int = 0


@dataclass
class Mamba2Parameter(Message):
    """TPU-native extension: a Mamba-2 mixer (SSD, arXiv:2405.21060;
    layers/sequence.py Mamba2, ops/ssd.py). Over a bottom (N, S, C): one
    input product to [z | x B C | dt] of widths inner | inner + 2 groups
    state | heads, inner = num_heads * head_dim (NOT a multiple of C); a
    depthwise causal convolution of `conv_kernel` taps and a bias over x B
    C, then SiLU; the selective recurrence S_t = exp(delta_t A) S_{t-1} +
    delta_t x_t (x) B_t, y_t = S_t C_t + D x_t a head, delta = softplus(dt
    + dt_bias), A = -exp(A_log), head h reading B and C of group h //
    (num_heads / groups), computed in chunks of `chunk` positions with the
    decays and the carried state in float32; y * silu(z), then an RMS norm
    over each of the `groups` groups of inner / groups channels apart,
    times a learned scale; one output product. No bias on the products.
    Blobs: in_weight, conv_weight (channels, taps: the LAST tap reads the
    current position), conv_bias, dt_bias, A_log, D, norm_scale,
    out_weight."""
    num_heads: int = 0
    head_dim: int = 0
    state_size: int = 0
    groups: int = 1
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5
    # the two products' filler (default xavier). The rest starts as the
    # published model's does: A_log_h = ln(h + 1), D = 1, the norm's scale
    # 1, the convolution's taps uniform in +- 1 / sqrt(conv_kernel) and its
    # bias 0, and dt_bias such that softplus(dt_bias) is log-uniform in
    # [dt_min, dt_max], floored at dt_floor (a published config's
    # time_step_min, time_step_max, time_step_floor)
    weight_filler: FillerParameter | None = None
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


@dataclass
class BlockDiffusionParameter(Message):
    """TPU-native extension: the noise of block-diffusion training
    (arXiv:2503.09573; layers/sequence.py BlockDiffusionNoise). Of a
    bottom of token ids (N, L) in blocks of block_length, each block b
    draws t_b ~ U(t_min, 1) and masks each of its tokens independently
    with probability t_b. Tops: ids (N, 2 L) = [noisy | clean], mask_id
    where masked; labels (N, L), the clean id where masked and
    ignore_label elsewhere; weights (N, L), 1 / t_b where masked and 0
    elsewhere; optionally the count of masked positions. The draw comes
    from the layer's per-step rng, fresh every step."""
    block_length: int = 1
    mask_id: int = 0
    t_min: float = 1e-3
    ignore_label: int = -1


@dataclass
class HingeLossParameter(Message):
    norm: str = "L1"  # L1 / L2


@dataclass
class InfogainLossParameter(Message):
    source: str = ""


@dataclass
class ContrastiveLossParameter(Message):
    margin: float = 1.0
    legacy_version: bool = False


@dataclass
class EltwiseParameter(Message):
    operation: str = "SUM"  # PROD / SUM / MAX
    coeff: list[float] = _rep()
    stable_prod_grad: bool = True


@dataclass
class ConcatParameter(Message):
    axis: int = 1
    concat_dim: int = 1  # legacy


@dataclass
class SliceParameter(Message):
    axis: int = 1
    slice_point: list[int] = _rep()
    slice_dim: int = 1  # legacy


@dataclass
class FlattenParameter(Message):
    axis: int = 1
    end_axis: int = -1


@dataclass
class ReshapeParameter(Message):
    shape: BlobShape | None = None
    axis: int = 0
    num_axes: int = -1


@dataclass
class CropParameter(Message):
    axis: int = 2
    offset: list[int] = _rep()


@dataclass
class TileParameter(Message):
    axis: int = 1
    tiles: int = 0


@dataclass
class ReductionParameter(Message):
    operation: str = "SUM"  # SUM / ASUM / SUMSQ / MEAN
    axis: int = 0
    coeff: float = 1.0


@dataclass
class ArgMaxParameter(Message):
    out_max_val: bool = False
    top_k: int = 1
    axis: int | None = None


@dataclass
class EmbedParameter(Message):
    num_output: int = 0
    input_dim: int = 0
    bias_term: bool = True
    weight_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None


@dataclass
class SPPParameter(Message):
    pyramid_height: int = 0
    pool: str = "MAX"
    engine: str = "DEFAULT"


@dataclass
class RecurrentParameter(Message):
    num_output: int = 0
    weight_filler: FillerParameter | None = None
    bias_filler: FillerParameter | None = None
    debug_info: bool = False
    expose_hidden: bool = False


@dataclass
class ClassMapping(Message):
    """object_class entry: dataset class id `src` -> coverage index `dst`
    (reference caffe.proto ClassMapping)."""
    src: int = 0
    dst: int = 0


@dataclass
class DetectNetGroundTruthParameter(Message):
    """Coverage-grid generation config (reference caffe.proto:511-549)."""
    stride: int = 4
    scale_cvg: float = 0.5
    gridbox_type: str = "GRIDBOX_MAX"
    max_cvg_len: int = 50
    min_cvg_len: int = 50
    coverage_type: str = "RECTANGULAR"
    image_size_x: int = 1248
    image_size_y: int = 384
    obj_norm: bool = False
    crop_bboxes: bool = True
    object_class: list[ClassMapping] = _rep()


@dataclass
class DetectNetAugmentationParameter(Message):
    """Detection augmentation config (reference caffe.proto:552-583)."""
    crop_prob: float = 1.0
    shift_x: int = 0
    shift_y: int = 0
    scale_prob: float = 0.33
    scale_min: float = 0.7
    scale_max: float = 1.0
    flip_prob: float = 0.33
    rotation_prob: float = 0.33
    max_rotate_degree: float = 1.0
    hue_rotation_prob: float = 0.33
    hue_rotation: float = 15.0
    desaturation_prob: float = 0.33
    desaturation_max: float = 0.5


@dataclass
class TransformationParameter(Message):
    """Data augmentation config (caffe.proto TransformationParameter;
    applied by the reference's DataTransformer, data_transformer.cpp)."""
    scale: float = 1.0
    mirror: bool = False
    crop_size: int = 0
    mean_file: str = ""
    mean_value: list[float] = _rep()
    force_color: bool = False
    force_gray: bool = False
    # NVCaffe extras
    use_gpu_transform: bool = False
    random_seed: int = -1


@dataclass
class DataParameter(Message):
    source: str = ""
    batch_size: int = 0
    rand_skip: int = 0
    backend: str = "LEVELDB"  # LEVELDB / LMDB
    scale: float = 1.0  # legacy transform fields
    mean_file: str = ""
    crop_size: int = 0
    mirror: bool = False
    force_encoded_color: bool = False
    prefetch: int = 4
    # NVCaffe extras: threads & cache
    threads: int = 0
    parser_threads: int = 0
    cache: bool = False
    shuffle: bool = False


@dataclass
class ImageDataParameter(Message):
    source: str = ""
    batch_size: int = 1
    rand_skip: int = 0
    shuffle: bool = False
    new_height: int = 0
    new_width: int = 0
    is_color: bool = True
    scale: float = 1.0
    mean_file: str = ""
    crop_size: int = 0
    mirror: bool = False
    root_folder: str = ""


@dataclass
class MemoryDataParameter(Message):
    batch_size: int = 0
    channels: int = 0
    height: int = 0
    width: int = 0


@dataclass
class HDF5DataParameter(Message):
    source: str = ""
    batch_size: int = 0
    shuffle: bool = False


@dataclass
class HDF5OutputParameter(Message):
    file_name: str = ""


@dataclass
class WindowDataParameter(Message):
    source: str = ""
    scale: float = 1.0
    mean_file: str = ""
    batch_size: int = 0
    crop_size: int = 0
    mirror: bool = False
    fg_threshold: float = 0.5
    bg_threshold: float = 0.5
    fg_fraction: float = 0.25
    context_pad: int = 0
    crop_mode: str = "warp"
    cache_images: bool = False
    root_folder: str = ""


@dataclass
class DummyDataParameter(Message):
    data_filler: list[FillerParameter] = _rep()
    shape: list[BlobShape] = _rep()
    num: list[int] = _rep()  # legacy 4D
    channels: list[int] = _rep()
    height: list[int] = _rep()
    width: list[int] = _rep()


@dataclass
class InputParameter(Message):
    shape: list[BlobShape] = _rep()


@dataclass
class PythonParameter(Message):
    module: str = ""
    layer: str = ""
    param_str: str = ""
    share_in_parallel: bool = False


@dataclass
class BatchReindexParameter(Message):
    pass


@dataclass
class FilterParameter(Message):
    pass


# ---------------------------------------------------------------------------
# LayerParameter
# ---------------------------------------------------------------------------

@dataclass
class PipelineParameter(Message):
    """TPU-native extension (no reference analogue — SURVEY §2.7: PP
    absent, ForwardFromTo is a sequential one-device loop): a stack of
    `num_stages` STRUCTURALLY IDENTICAL blocks, each block being the
    repeated `layer {...}` sub-graph, executed as a GPipe shift-register
    over the mesh 'model' axis (parallel/pipeline.py). Under a mesh whose
    model axis equals num_stages the batch is split into `micro_batches`
    microbatches and stage s's weights live only on mesh position s; on a
    single device the same stacked params run as a sequential lax.scan —
    bit-identical math either way."""
    num_stages: int = 0
    micro_batches: int = 1
    layer: list[LayerParameter] = _rep()


@dataclass
class LayerParameter(Message):
    """One op instance in the graph (caffe.proto LayerParameter:368-480)."""
    name: str = ""
    type: str = ""
    bottom: list[str] = _rep()
    top: list[str] = _rep()
    phase: str = ""
    loss_weight: list[float] = _rep()
    param: list[ParamSpec] = _rep()
    propagate_down: list[bool] = _rep()
    include: list[NetStateRule] = _rep()
    exclude: list[NetStateRule] = _rep()

    # NVCaffe per-layer precision selection (caffe.proto:374-382):
    # FLOAT/FLOAT16/DOUBLE. FLOAT16 maps to bfloat16 on TPU.
    forward_type: str = ""
    backward_type: str = ""
    forward_math: str = ""
    backward_math: str = ""
    debug: bool = False
    # TPU-native extension: rematerialize this layer's activations in the
    # backward pass (jax.checkpoint) instead of storing them — the
    # HBM-for-FLOPs trade the reference cannot express
    remat: bool = False
    # TPU-native extension: tensor-parallel placement of this layer's
    # weights over the mesh 'model' axis. "rows" shards the output dim
    # (Megatron column-parallel), "cols" the input dim (row-parallel,
    # XLA inserts the partial-sum all-reduce). Consumed by the Solver
    # when a mesh with a model axis is active; ignored otherwise.
    param_sharding: str = ""

    transform_param: TransformationParameter | None = None
    loss_param: LossParameter | None = None

    accuracy_param: AccuracyParameter | None = None
    attention_param: AttentionParameter | None = None
    argmax_param: ArgMaxParameter | None = None
    batch_norm_param: BatchNormParameter | None = None
    bias_param: BiasParameter | None = None
    concat_param: ConcatParameter | None = None
    contrastive_loss_param: ContrastiveLossParameter | None = None
    convolution_param: ConvolutionParameter | None = None
    crop_param: CropParameter | None = None
    data_param: DataParameter | None = None
    detectnet_groundtruth_param: DetectNetGroundTruthParameter | None = None
    detectnet_augmentation_param: DetectNetAugmentationParameter | None = None
    dropout_param: DropoutParameter | None = None
    dummy_data_param: DummyDataParameter | None = None
    eltwise_param: EltwiseParameter | None = None
    moe_param: MoEParameter | None = None
    mamba2_param: Mamba2Parameter | None = None
    block_diffusion_param: BlockDiffusionParameter | None = None
    layer_norm_param: LayerNormParameter | None = None
    rms_norm_param: RMSNormParameter | None = None
    parameter_param: ParameterParameter | None = None
    elu_param: ELUParameter | None = None
    embed_param: EmbedParameter | None = None
    exp_param: ExpParameter | None = None
    flatten_param: FlattenParameter | None = None
    hdf5_data_param: HDF5DataParameter | None = None
    hdf5_output_param: HDF5OutputParameter | None = None
    hinge_loss_param: HingeLossParameter | None = None
    image_data_param: ImageDataParameter | None = None
    infogain_loss_param: InfogainLossParameter | None = None
    inner_product_param: InnerProductParameter | None = None
    input_param: InputParameter | None = None
    log_param: LogParameter | None = None
    lrn_param: LRNParameter | None = None
    memory_data_param: MemoryDataParameter | None = None
    mvn_param: MVNParameter | None = None
    pipeline_param: PipelineParameter | None = None
    pooling_param: PoolingParameter | None = None
    power_param: PowerParameter | None = None
    prelu_param: PReLUParameter | None = None
    python_param: PythonParameter | None = None
    recurrent_param: RecurrentParameter | None = None
    reduction_param: ReductionParameter | None = None
    relu_param: ReLUParameter | None = None
    reshape_param: ReshapeParameter | None = None
    scale_param: ScaleParameter | None = None
    sigmoid_param: SigmoidParameter | None = None
    slice_param: SliceParameter | None = None
    softmax_param: SoftmaxParameter | None = None
    spp_param: SPPParameter | None = None
    tanh_param: TanHParameter | None = None
    threshold_param: ThresholdParameter | None = None
    tile_param: TileParameter | None = None
    window_data_param: WindowDataParameter | None = None


# ---------------------------------------------------------------------------
# NetParameter
# ---------------------------------------------------------------------------

@dataclass
class NetParameter(Message):
    """Whole-graph definition (caffe.proto NetParameter:88-146)."""
    name: str = ""
    input: list[str] = _rep()  # legacy "input"/"input_shape"/"input_dim"
    input_shape: list[BlobShape] = _rep()
    input_dim: list[int] = _rep()
    force_backward: bool = False
    state: NetState | None = None
    debug_info: bool = False
    layer: list[LayerParameter] = _rep()
    layers: list[LayerParameter] = _rep()  # legacy V1 field name

    # NVCaffe net-wide precision defaults (caffe.proto:124-127)
    default_forward_type: str = "FLOAT"
    default_backward_type: str = "FLOAT"
    default_forward_math: str = ""
    default_backward_math: str = ""
    # fp16 loss scaling (caffe.proto:130; applied net.cpp:815-818)
    global_grad_scale: float = 1.0
    default_conv_algos_override: str = ""
    # gradient-reduction bucket count (caffe.proto:140, consumed by
    # net.cpp:824-863). Default bucket count for the overlapped bucketed
    # reduction plane (ISSUE 6, parallel/reduction.py) when the solver
    # does not override it; the default GSPMD path still lets XLA place
    # the collectives. 0/negative is rejected at Solver init — this knob
    # is no longer accept-and-ignore.
    reduce_buckets: int = 6


# ---------------------------------------------------------------------------
# SolverParameter
# ---------------------------------------------------------------------------

@dataclass
class SolverParameter(Message):
    """Training configuration (caffe.proto SolverParameter:147-301)."""
    net: str = ""
    net_param: NetParameter | None = None
    train_net: str = ""
    test_net: list[str] = _rep()
    train_net_param: NetParameter | None = None
    test_net_param: list[NetParameter] = _rep()
    train_state: NetState | None = None
    test_state: list[NetState] = _rep()

    test_iter: list[int] = _rep()
    test_interval: int = 0
    test_compute_loss: bool = False
    test_initialization: bool = True

    base_lr: float = 0.01
    display: int = 0
    average_loss: int = 1
    max_iter: int = 0
    iter_size: int = 1

    lr_policy: str = "fixed"
    gamma: float = 0.0
    power: float = 0.0
    momentum: float = 0.0
    weight_decay: float = 0.0
    regularization_type: str = "L2"
    stepsize: int = 0
    stepvalue: list[int] = _rep()
    clip_gradients: float = -1.0
    min_lr: float = 0.0

    # large-batch warmup (NVCaffe caffe.proto:193-195; sgd_solver.cpp:27-33)
    rampup_interval: int = 0
    rampup_lr: float = 0.0
    # momentum policy (caffe.proto:228-230; sgd_solver.cpp:67-91)
    momentum_policy: str = "fixed"
    max_momentum: float = 0.0
    momentum_power: float = 1.0
    momentum2: float = 0.999
    rms_decay: float = 0.99
    delta: float = 1e-8

    snapshot: int = 0
    snapshot_prefix: str = ""
    snapshot_diff: bool = False
    snapshot_format: str = "BINARYPROTO"
    snapshot_after_train: bool = True

    solver_mode: str = "GPU"
    device_id: int = 0
    random_seed: int = -1

    type: str = "SGD"
    solver_type: Any = ""  # legacy enum: identifier (ADAM) or number (5)
    debug_info: bool = False

    # fp16 master-weight storage (caffe.proto:299)
    solver_data_type: str = "FLOAT"
    # loss scaling for fp16 grads (net-level global_grad_scale mirror)
    global_grad_scale: float = 1.0

    # data layer hint fields (NVCaffe)
    min_plateau_lr: float = 0.0
    plateau_winsize: list[int] = _rep()

    # TPU-native extension: device mesh shape for pjit sharding, replacing
    # the reference's mpirun/GPU-list topology flags.
    mesh_data_axis: int = 0
    # TPU-native extension (beyond the reference): 1 = shard optimizer
    # slots over the 'data' mesh axis (ZeRO-1) — grads reduce-scatter,
    # updates compute on 1/N of each param, new params all-gather; slot
    # memory drops to 1/N per chip. 0 = replicated (reference behavior).
    zero_stage: int = 0
    # TPU-native extension: fuse up to K consecutive iterations into ONE
    # jitted lax.scan program fed by a device-resident super-batch — the
    # host pays one dispatch per K iterations instead of
    # per iteration. Chunks auto-shrink to land exactly on display /
    # test_interval / snapshot boundaries. 1 (default) = classic
    # one-dispatch-per-iteration behavior.
    step_chunk: int = 1
    # TPU-native extension (ISSUE 2): test batches fused into ONE
    # evaluation dispatch — the test pass runs as a jitted lax.scan over
    # a [T, B, ...] super-batch carrying the per-blob score accumulators
    # in HBM, ceil(test_iter/T) dispatches per pass instead of
    # test_iter. 0 (default) = auto-size T from the eval super-batch
    # HBM budget (solver._test_chunk_len); >0 pins T explicitly.
    test_chunk: int = 0
    # TPU-native extension (ISSUE 3, survivable training): keep only the
    # newest N snapshots on disk, GC'ing older ones after each write —
    # but never deleting the newest VERIFIED snapshot (resume must
    # always have somewhere to land). 0 (default) = keep everything,
    # the reference behavior.
    snapshot_keep: int = 0
    # TPU-native extension (ISSUE 4, self-healing training): on-device
    # non-finite guard inside the (fused) train step. When true, an
    # all-finite reduction over loss + gradients selects per step
    # between applying the optimizer update and keeping params /
    # momentum / BN state unchanged (skip-step) — zero extra dispatches,
    # the decision and its counters live in the scan carry. false
    # (default) = today's behavior, bitwise.
    train_guard: bool = False
    # consecutive skipped steps before the run declares numeric
    # divergence: journals the anomaly to <prefix>.run.json and exits
    # code 88 (EXIT_NUMERIC) so the --max-restarts supervisor can apply
    # anomaly_action. 0 = never exit (skip forever, counters only).
    guard_max_skips: int = 3
    # on-device loss-spike detector: >0 also skips a step whose loss
    # exceeds guard_loss_spike x the carried loss EMA (a divergence that
    # never goes non-finite). 0 (default) = finiteness checks only.
    guard_loss_spike: float = 0.0
    # decay of the loss EMA the spike detector compares against; the EMA
    # only absorbs ACCEPTED steps, so a diverging tail can't drag the
    # baseline up after it.
    guard_ema_decay: float = 0.9
    # what the supervisor does when the child exits 88:
    #   rewind    — restart from the newest verified snapshot (default)
    #   rewind_lr — rewind AND scale base_lr by anomaly_lr_mult per
    #               numeric restart (compounding), to step around the
    #               divergence instead of replaying into it
    #   abort     — treat divergence as fatal: no restart, exit 88
    anomaly_action: str = "rewind"
    anomaly_lr_mult: float = 0.1
    # TPU-native extension (ISSUE 6, overlapped bucketed gradient
    # reduction — parallel/reduction.py, the reference ReduceAndUpdate
    # plane net.cpp:757-913): when true, the data-parallel train step
    # computes gradients per device under shard_map and reduces them
    # with ONE lax.psum per contiguous bucket (reverse topological
    # layer order — the order backward produces them), so the TPU
    # scheduler can hoist each bucket's collective over the remaining
    # backward. false (default) = GSPMD-implicit reduction, today's
    # behavior; nets the per-device backward cannot express bitwise
    # (BatchNorm/MoE/host-callback/data-dependent loss normalization)
    # fall back to implicit with a warning.
    reduce_overlap: bool = False
    # bucket count for the overlapped reduction: 0 (default) inherits
    # the net-level reduce_buckets (reference default 6); explicit
    # 0/negative values are rejected. Ignored when grad_bucket_mb sets
    # a byte budget instead.
    reduce_buckets: int = 0
    # alternative bucket sizing: pack buckets up to this many MiB of
    # gradient bytes (a single larger param gets its own bucket, with a
    # warning). 0 (default) = use the bucket count. Negative rejected;
    # setting both this and reduce_buckets is an error.
    grad_bucket_mb: float = 0.0
    # TPU-native extension (ISSUE 9, mixed-precision bf16 training —
    # docs/benchmarks.md "Mixed-precision bf16 training"): whole-run
    # compute precision. "f32" (default) = today's behavior, bitwise.
    # "bf16" = activations and gradients compute in bfloat16 (the TPU
    # MXU's native 16-bit format) while parameters and optimizer slots
    # stay f32 MASTER copies — params cast to bf16 at use inside the
    # step, updates applied in f32 — threaded through Net compile, the
    # fused K-step scan, fused eval, and reduce_overlap (buckets pack
    # and psum in bf16, halving collective bytes; post-psum math in
    # f32). Orthogonal to the per-layer forward_type/backward_type
    # overrides, which still win where set.
    precision: str = "f32"
    # loss scaling for the bf16 backward (consumed only when precision
    # is bf16): 0 (default) = DYNAMIC — the scale rides the train-scan
    # carry, halves on a non-finite (overflow) step (which is SKIPPED,
    # not applied, and never trips the exit-88 divergence policy until
    # the scale is already at its floor), and doubles again after
    # loss_scale_window consecutive clean steps. > 0 = that fixed
    # static scale (grads unwound by 1/scale in f32 before the update).
    loss_scale: float = 0.0
    # consecutive clean (non-overflow) steps before the dynamic loss
    # scale grows 2x (capped); ignored for static scales.
    loss_scale_window: int = 200
    # TPU-native extension (ISSUE 10, native ingestion fast path —
    # docs/benchmarks.md "Ingestion"): budget in MiB for the bounded
    # decoded-record cache tier (data/datasets.py DecodedCacheDataset).
    # > 0 wraps every DB-backed data layer's dataset so post-decode,
    # pre-augment uint8 records are kept in RAM up to the budget —
    # epochs after the first skip DB read + crc verify + JPEG/PNG
    # decode for the cached span (admission is first-fit by record
    # index: deterministic, no LRU thrash under epoch shuffle).
    # 0 (default) = off; `data_param { cache: true }` (the reference's
    # whole-DB DataCache) takes precedence where set. The companion
    # env CAFFE_NATIVE_DECODE=0/1 forces the PIL/native decoder for
    # A/B runs (unset = native when built).
    decoded_cache_mb: float = 0.0
    # TPU-native extension (ISSUE 3): dispatch watchdog deadline in
    # seconds. >0 arms a monitor thread that journals the run state and
    # hard-exits (exit code 86) when any device dispatch/harvest blocks
    # longer than this — a hung device call sits inside C++ where
    # no Python signal can interrupt, so this is the only way a hung run
    # becomes a bounded, supervisable failure. Must exceed the worst
    # jit-compile time a dispatch can trigger. 0 (default) = no
    # watchdog, the reference behavior.
    watchdog_deadline: float = 0.0
    # TPU-native extension (ISSUE 11, elastic multi-host training —
    # docs/robustness.md "Multi-host elasticity"): number of host
    # processes in the cluster (the reference's mpirun -n,
    # clusters.cpp:8-45). > 1 makes `caffe train` initialize
    # jax.distributed against `coordinator` (retry/backoff bounded;
    # failure journals and exits 87) so the device mesh spans every
    # host, reduce_overlap buckets become cross-host collectives, and
    # the Feeder stripes records per host. 0/1 (default) = single
    # process, today's behavior. Env fallbacks: CAFFE_TPU_NUM_HOSTS /
    # CAFFE_TPU_COORDINATOR / CAFFE_TPU_HOST_ID.
    hosts: int = 0
    # coordination-service address (host:port of host 0) for the
    # multi-host cluster; required when hosts > 1.
    coordinator: str = ""
    # cross-host heartbeat deadline in seconds: > 0 (with hosts > 1)
    # arms host-loss detection on the watchdog monitor thread — a peer
    # host silent this long is journaled to <prefix>.run.json and the
    # local worker exits 87 (EXIT_CLUSTER) for the supervisor's
    # coordinated restart, instead of hanging inside the next
    # collective. 0 (default) = no heartbeat.
    host_deadline: float = 0.0
    # TPU-native extension (ISSUE 19, degraded-mode elasticity —
    # docs/robustness.md "Degraded-mode elasticity"): quorum floor for
    # continuing after a PERMANENT host loss. > 0 (with hosts > 1 and
    # a supervisor, --max-restarts) lets the surviving supervisors run
    # the generation protocol: after exit 87 the lowest surviving host
    # collects supervisor beats for ~host_deadline, publishes
    # generation g+1 (surviving host set, remapped contiguous ranks,
    # new world W' >= min_hosts, fresh coordinator epoch) to the shared
    # <prefix>.cluster/ directory, and every survivor restarts its
    # worker at `-hosts W' -host_id k'` with `--resume auto` — rank 0
    # restores the last verified snapshot resharded onto the smaller
    # mesh and the Feeder re-stripes at W'. A revived host parks in
    # rejoin-wait; rank 0 re-admits it at the next snapshot boundary
    # via a grow-back generation. 0 (default) = off: today's
    # restart-all-at-same-world semantics, bitwise.
    min_hosts: int = 0


# ---------------------------------------------------------------------------
# ServingParameter (ISSUE 7 — no reference analogue: the reference's
# deployment story is the Flask web demo + extract_features, both
# configured ad hoc; here the serving plane's knobs are schema like
# every other parameter surface so recipes can pin them)
# ---------------------------------------------------------------------------

@dataclass
class ServingParameter(Message):
    """Inference-serving configuration (caffe_mpi_tpu/serving/,
    docs/serving.md). Parsed from a prototxt via the usual Message
    machinery or built by the `caffe serve` CLI flags."""
    # continuous-batching window in milliseconds: a batch closes when
    # this long has passed since its FIRST request arrived, or earlier
    # when a full max-size bucket is waiting. 0 = dispatch immediately
    # (no batching beyond what is already queued).
    serve_window_ms: float = 5.0
    # explicit padded-batch bucket ladder, comma-separated ("1,4,16");
    # every bucket is AOT-compiled at model load so arrival-size
    # variance never recompiles. "" (default) = geometric 1,4,16,...
    # up to the deploy prototxt's declared batch.
    serve_buckets: str = ""
    # HBM budget (MiB) for device-resident model weights across the
    # zoo; exceeding it spills the least-recently-used model's params
    # to the host master copy (compiled programs survive a spill).
    # 0 (default) = unlimited, everything stays resident.
    serve_hbm_mb: float = 0.0
    # compute precision for this model's bucket programs (ISSUE 9):
    # "f32" (default) = today's behavior; "bf16" = the bucket forwards
    # compute in bfloat16 (scores cast back to f32 at the program
    # boundary, so the classify/detect surfaces are unchanged). The
    # ladder is compiled once per model either way — a dtype choice is
    # load-time, so steady-state serving still performs ZERO compiles.
    serve_dtype: str = "f32"
    # load-shedding admission control (ISSUE 12): bound on the
    # per-engine request backlog. A submit arriving with this many
    # requests already pending fails FAST with a typed ShedError
    # (HTTP 429) instead of growing an unbounded queue whose every
    # entry will miss its deadline anyway. 0 (default) = unbounded,
    # today's behavior.
    serve_queue_limit: int = 0
    # per-request deadline in milliseconds (ISSUE 12): a request whose
    # batch cannot dispatch within this long of its arrival fails with
    # a typed DeadlineError (HTTP 504) at window close instead of aging
    # in the queue; the batching window is also clamped to it so a
    # batch never *waits* past its head request's deadline. 0 (default)
    # = no deadline, today's behavior (zero per-request cost when off).
    serve_deadline_ms: float = 0.0
    # dispatch stall breaker deadline in seconds (ISSUE 12): > 0 arms a
    # resilience.DispatchWatchdog over the serving dispatch/harvest
    # device sections — a device call blocked this long
    # fails the in-flight futures with DeadlineError, journals to
    # `<model>.serve.run.json`, and flips the engine unhealthy so new
    # requests shed immediately (HTTP 503) instead of hanging; a
    # recovery probe re-arms it. 0 (default) = breaker off.
    serve_stall_s: float = 0.0
    # hot-content decoded-request cache budget in MiB (ISSUE 14, native
    # serving ingest — docs/serving.md "Native request ingest"): > 0
    # keeps decoded request images in RAM keyed by the crc32c of their
    # ENCODED bytes (LRU by content hash — the same hot image arrives
    # under many requests; hits are exact-bytes-verified, so a 32-bit
    # crc collision decodes fresh instead of serving another image's
    # pixels), so repeats skip JPEG/PNG decode entirely
    # (`decode_calls` provably unmoved; counters in engine.stats()
    # /stats). The `decoded_cache_mb` solver knob's machinery applied
    # request-side. 0 (default) = cache off. The companion env
    # CAFFE_NATIVE_DECODE=0/1 forces the PIL/native request decoder for
    # A/B runs, exactly as on the training ingest path.
    serve_decoded_cache_mb: float = 0.0
    # persistent AOT program bank directory (ISSUE 17, docs/serving.md
    # "Program bank"): after each bucket warm the compiled XLA
    # executable is serialized into this directory under a fingerprint
    # of model topology + bucket + dtype + jax/jaxlib/backend version,
    # published verified-atomically (crc32c sidecar manifest written
    # last). A bank-warm engine start deserializes its whole ladder
    # with ZERO compiles (`compile_count == bank_misses`, counters in
    # engine.stats()["bank"] /stats); any torn/rotten/stale entry is a
    # counted miss that recompiles and repopulates, never a crash.
    # "" (default) = bank off, today's behavior.
    serve_program_bank: str = ""
    # serving fleet size (ISSUE 18, docs/serving.md "Fleet"): N >= 1
    # runs N ServingEngine replica PROCESSES — each bank-warmed via
    # serve_program_bank, so a supervised respawn is zero-compile —
    # behind a least-loaded router that retries typed 429/503 sheds on
    # a healthy sibling, aggregates /stats + /healthz fleet-wide, and
    # treats a dead replica like a dead training host: heartbeat-
    # detected, drained from rotation, respawned, re-admitted only
    # after its readyz gate. 0 (default) = classic single-process
    # serving, today's behavior.
    serve_replicas: int = 0
    # per-request sibling-retry budget for the fleet router (ISSUE 18):
    # how many OTHER replicas a typed-retryable failure (429 shed,
    # 503 unhealthy/closed, a dead replica's connection error) may be
    # retried on before the failure goes typed to the client. A 504
    # deadline or 400 bad-request is NEVER retried — the deadline is
    # already spent / the bytes are the client's fault on every
    # sibling. Default 1: one sibling absorbs a shed.
    serve_retry_budget: int = 1
    # replica heartbeat deadline in seconds (ISSUE 18): each replica
    # publishes beats to the fleet directory; one silent this long is
    # a DEAD REPLICA — drained from rotation (in-flight requests
    # resolve typed via the retry path), journaled `replica_dead`,
    # respawned, and re-admitted after /readyz. The host_deadline
    # machinery (resilience.HostHeartbeat over DirBeatTransport)
    # applied to the serving plane. Default 5 s.
    replica_deadline: float = 5.0


SOLVER_TYPE_NAMES = {
    # legacy solver_type enum value -> modern type string
    "SGD": "SGD", "NESTEROV": "Nesterov", "ADAGRAD": "AdaGrad",
    "RMSPROP": "RMSProp", "ADADELTA": "AdaDelta", "ADAM": "Adam",
    "0": "SGD", "1": "Nesterov", "2": "AdaGrad",
    "3": "RMSProp", "4": "AdaDelta", "5": "Adam",
}


def solver_type(solver: SolverParameter) -> str:
    """Resolve modern `type` vs legacy `solver_type` enum
    (reference: upgrade_proto.cpp UpgradeSolverType, which forbids setting
    both and rejects unknown enum values)."""
    if solver.has("type") and solver.has("solver_type"):
        raise ValueError(
            "solver sets both 'type' and legacy 'solver_type'; remove one"
        )
    if not solver.has("solver_type"):
        return solver.type
    key = str(solver.solver_type).upper()
    if key not in SOLVER_TYPE_NAMES:
        raise ValueError(f"unknown legacy solver_type {solver.solver_type!r}")
    return SOLVER_TYPE_NAMES[key]
