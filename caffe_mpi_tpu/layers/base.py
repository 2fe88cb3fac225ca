"""Layer abstraction + registry — functional replacement for the reference's
LayerBase/Layer<Ftype,Btype> class hierarchy and LayerRegistry.

The reference's layers are stateful C++ objects with Forward_gpu/Backward_gpu
CUDA implementations dispatched through a factory
(include/caffe/layer.hpp:43-549, src/caffe/layer_factory.cpp). On TPU the
backward pass comes from `jax.grad` over a pure forward function, so a layer
here is: shape inference (`setup`) + parameter declaration (`param_decls`) +
a pure `apply(params, state, bottoms) -> (tops, new_state)`. The whole net
composes into one jit-compiled function; XLA replaces the per-layer kernel
dispatch, stream management, and cuDNN algorithm selection.

Caffe's positional param blobs (blobs_[0]=weight, blobs_[1]=bias...) are kept
as an *ordered* dict so .caffemodel import/export can map by position.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..core.fillers import fill
from ..core.types import DtypePolicy
from ..proto.config import FillerParameter, LayerParameter

Shape = tuple[int, ...]


@dataclass
class ParamDecl:
    """One learnable blob: shape + init + training multipliers.

    Mirrors the union of the reference's Blob allocation in each layer's
    LayerSetUp and the per-param ParamSpec (lr_mult/decay_mult) resolution
    in Net::AppendParam (net.cpp:501-667)."""
    shape: Shape
    filler: FillerParameter | None = None
    lr_mult: float = 1.0
    decay_mult: float = 1.0
    shared_name: str = ""  # non-empty -> net-level weight sharing by name
    dtype: Any = None  # defaults to policy.master


class Layer:
    """Base class. Subclasses set `type_name` and implement setup/apply."""

    type_name: str = ""
    # the `checkpoint_name`s of what this layer's backward pass reads that
    # a `remat: true` keeps (Net.apply_range): everything else of the
    # layer's forward is computed again in the backward pass, these are not
    kept_under_remat: tuple[str, ...] = ()

    def __init__(self, lp: LayerParameter, policy: DtypePolicy, phase: str = "TRAIN"):
        self.lp = lp
        self.policy = policy
        self.phase = phase
        self.params: dict[str, ParamDecl] = {}
        self.in_shapes: list[Shape] = []
        self.out_shapes: list[Shape] = []
        # parallel.MeshPlan bound by Net.bind_mesh when the solver runs
        # SPMD; layers with distributed execution modes (Attention
        # sequence_parallel, Pipeline stages) read it at trace time
        self.mesh_plan = None

    # -- graph construction ------------------------------------------------
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        """Infer output shapes and declare params. Must be overridden."""
        raise NotImplementedError

    def declare(self, name: str, shape: Shape, filler: FillerParameter | None = None,
                param_idx: int | None = None, **kw) -> None:
        """Declare a learnable param; applies the prototxt `param {}` specs
        positionally like Net::AppendParam does."""
        idx = len(self.params) if param_idx is None else param_idx
        decl = ParamDecl(shape=shape, filler=filler, **kw)
        if idx < len(self.lp.param):
            spec = self.lp.param[idx]
            decl.lr_mult = spec.lr_mult
            decl.decay_mult = spec.decay_mult
            decl.shared_name = spec.name
        self.params[name] = decl

    # -- initialization ----------------------------------------------------
    def init_params(self, key: jax.Array) -> dict[str, jax.Array]:
        out = {}
        for i, (name, decl) in enumerate(self.params.items()):
            dtype = decl.dtype if decl.dtype is not None else self.policy.master
            out[name] = fill(decl.filler, jax.random.fold_in(key, i), decl.shape,
                             dtype)
        return out

    def init_state(self) -> dict[str, jax.Array]:
        """Non-learnable mutable state (e.g. BN running stats)."""
        return {}

    # -- execution ---------------------------------------------------------
    def apply(self, params: dict, state: dict, bottoms: Sequence[jax.Array], *,
              train: bool, rng: jax.Array | None):
        """Pure forward. Returns (tops: list, new_state: dict)."""
        raise NotImplementedError

    # -- interop -----------------------------------------------------------
    def caffe_blobs(self) -> list[tuple[str, str]]:
        """Ordered ('param'|'state', name) pairs matching the reference
        layer's positional blobs_ vector — the .caffemodel contract.
        Default: declared params in order (weight, bias for most layers)."""
        return [("param", n) for n in self.params]

    # -- conveniences ------------------------------------------------------
    @property
    def name(self) -> str:
        return self.lp.name

    def f(self, x):
        """Cast to forward compute dtype."""
        return self.policy.cast_in(x)

    def is_loss(self) -> bool:
        return False

    def default_loss_weight(self, top_idx: int) -> float:
        return 0.0


# ---------------------------------------------------------------------------
# Registry (reference: LayerRegistry::CreateLayer, layer_factory.cpp:53-88)
# ---------------------------------------------------------------------------

LAYER_REGISTRY: dict[str, type[Layer]] = {}


def register(type_name: str):
    def deco(cls: type[Layer]) -> type[Layer]:
        if type_name in LAYER_REGISTRY:
            raise ValueError(f"layer type {type_name!r} already registered")
        cls.type_name = type_name
        LAYER_REGISTRY[type_name] = cls
        return cls
    return deco


def create_layer(lp: LayerParameter, policy: DtypePolicy, phase: str) -> Layer:
    try:
        cls = LAYER_REGISTRY[lp.type]
    except KeyError:
        known = ", ".join(sorted(LAYER_REGISTRY))
        raise ValueError(
            f"unknown layer type {lp.type!r} (layer {lp.name!r}); known: {known}"
        ) from None
    return cls(lp, policy, phase)


def registered_types() -> list[str]:
    """Reference: LayerRegistry list, exposed in pycaffe as layer_type_list."""
    return sorted(LAYER_REGISTRY)
