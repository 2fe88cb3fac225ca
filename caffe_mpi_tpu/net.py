"""Net — graph runtime. The functional replacement for reference net.cpp.

The reference's Net (src/caffe/net.cpp, 1,376 LoC) builds a layer DAG from
NetParameter, allocates blobs, runs sequential Forward/Backward loops with a
dedicated gradient-reduction thread, and manages a contiguous learnable-diff
space for bucketed NCCL allreduce (net.cpp:757-913, 1350-1374).

TPU-native design: the graph compiles into ONE pure function
  apply(params, state, feeds) -> (blobs, new_state, loss)
and the backward pass is jax.grad of that function inside a single jit-ted
train step. That one decision subsumes several reference subsystems:
- insert_splits.cpp         -> unnecessary (values are immutable, fan-out is free)
- reduce thread + buckets   -> XLA latency-hiding scheduler overlaps psum
                               with backward automatically
- learnable diff space      -> XLA's buffer assignment
- backward-need analysis    -> stop_gradient on lr_mult=0 params + XLA DCE
What remains faithful: layer declaration order IS execution order, in-place
tops, loss_weight semantics, param sharing by ParamSpec.name, phase filtering,
per-layer dtype policy.
"""

from __future__ import annotations

import copy
import logging
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from .core.types import DtypePolicy
from .layers import base as layer_base
from .layers.base import Layer, create_layer
from .layers.data_layers import InputLayerBase
from .proto.config import NetParameter, NetState
from .proto.upgrade import filter_net, normalize_net
from .utils import compile_cache, spans
from .utils.spans import layer_scope

log = logging.getLogger(__name__)

Params = dict[str, dict[str, jax.Array]]
State = dict[str, dict[str, jax.Array]]


class Net:
    """Build from a (filtered) NetParameter; compile via jit around apply()."""

    def __init__(self, param: NetParameter, phase: str = "TRAIN", *,
                 level: int = 0, stages: Sequence[str] = (),
                 batch_divisor: int = 1,
                 data_shape_probe=None, model_dir: str = "",
                 solver_storage: str = "FLOAT",
                 device_transform: bool | None = None,
                 precision: str = ""):
        """batch_divisor: divide data-layer batch sizes by the per-replica
        count, reproducing divide_batch_size (reference parallel.cpp:295-348).
        data_shape_probe: callable(layer_param) -> (C,H,W) for DB-backed
        layers whose shape comes from the dataset.
        model_dir: base directory for relative data-source paths (the
        directory of the prototxt, like the reference's working-dir
        convention).
        solver_storage: the solver's `solver_data_type` (caffe.proto:299) —
        the storage dtype of learnable params (master weights). FLOAT (f32,
        the default and the right TPU choice), FLOAT16 (bf16 storage;
        updates still accumulate in f32 — Solver casts up around the update
        rule), or DOUBLE (mapped to f32: no f64 MXU path). Integer types
        are rejected.
        device_transform: None (auto — in-graph crop/mean/mirror/scale for
        eligible Data layers, the use_gpu_transform analogue) or False to
        force the host transform path (manual-feed surfaces: pycaffe).
        precision: the solver-level compute-precision override (ISSUE 9,
        SolverParameter.precision). "" / "f32" (default) keeps the
        prototxt's own dtype declarations, bitwise. "bf16" makes the
        NET-LEVEL default forward/backward type FLOAT16 (-> bfloat16 on
        TPU) — the one-knob spelling of NVCaffe's fp16 prototxt variants
        — while per-layer forward_type/backward_type overrides still
        win, exactly as they do against the prototxt net defaults."""
        # the start-up ledger listens from the first Net on, where no
        # entry point enabled a compile cache before (utils/spans.py)
        compile_cache.install_ledger()
        with spans.phase("net/build", phase=phase) as built:
            self._build(param, phase, level, stages, batch_divisor,
                        data_shape_probe, model_dir, solver_storage,
                        device_transform, precision)
            built.stats["layers"] = len(self.layers)

    def _build(self, param, phase, level, stages, batch_divisor,
               data_shape_probe, model_dir, solver_storage, device_transform,
               precision) -> None:
        self.model_dir = model_dir
        param = normalize_net(param)
        state = NetState(phase=phase, level=level, stage=list(stages))
        param = filter_net(param, state)
        self.param = param
        self.phase = phase
        self.name = param.name

        self.layers: list[Layer] = []
        self._layer_index: dict[str, Layer] = {}
        self._indexed_upto = 0
        self.blob_shapes: dict[str, tuple] = {}
        self.feed_blobs: list[str] = []  # blob names fed from host
        # actual host-feed contract: key -> (shape, kind); differs from
        # blob_shapes for device-transform Data layers (raw uint8 + aug)
        self.feed_specs: dict[str, tuple[tuple, str]] = {}
        self.loss_blobs: list[tuple[str, float]] = []  # (blob, weight)
        self._loss_at: dict[str, int] = {}  # loss blob -> producing layer idx
        # param sharing: ParamSpec.name -> (owner layer, param name)
        self._shared_owner: dict[str, tuple[str, str]] = {}
        self.param_aliases: dict[tuple[str, str], tuple[str, str]] = {}

        if solver_storage not in ("", "FLOAT", "FLOAT16", "DOUBLE"):
            raise ValueError(
                f"unsupported solver_data_type {solver_storage!r}: learnable "
                "params must be floating point (FLOAT, FLOAT16, or DOUBLE)")
        solver_storage = solver_storage or "FLOAT"
        if precision not in ("", "f32", "bf16"):
            raise ValueError(f"unknown precision {precision!r} "
                             "(expected 'f32' or 'bf16')")
        # precision: bf16 rewrites the NET-LEVEL dtype defaults only —
        # resolution order (layer override > net default) is untouched,
        # so a prototxt that pins a layer to FLOAT keeps it f32
        net_fwd = param.default_forward_type
        net_bwd = param.default_backward_type
        if precision == "bf16":
            net_fwd = "FLOAT16" if not param.has("default_forward_type") \
                else net_fwd
            net_bwd = "FLOAT16" if not param.has("default_backward_type") \
                else net_bwd
            if "FLOAT16" not in (net_fwd, net_bwd):
                # the knob lost to explicit prototxt defaults on BOTH
                # sides: say so, or `-precision bf16` silently trains
                # f32 (loss scaling armed for nothing, speedup ~1.0)
                log.warning(
                    "precision: bf16 requested, but the net prototxt "
                    "explicitly sets default_forward_type/"
                    "default_backward_type (%s/%s) and the prototxt "
                    "wins — bf16 did not engage net-wide (per-layer "
                    "forward_type overrides may still apply)",
                    net_fwd, net_bwd)
        from .proto.netshape import BF16_INELIGIBLE
        for lp in param.layer:
            policy = DtypePolicy.resolve(
                lp.forward_type, lp.backward_type,
                net_fwd, net_bwd,
                solver_storage,
                lp.forward_math, param.default_forward_math,
                lp.backward_math, param.default_backward_math,
            )
            if policy.forward == jnp.bfloat16 and lp.type in BF16_INELIGIBLE:
                # one registry with netlint's net-dtype pass (ISSUE 15):
                # host-callback/IO layers run f32 buffers regardless, so
                # a bf16 request here is silently not honored — warn at
                # build (netlint flags the same statically)
                log.warning(
                    "layer %s (%s): FLOAT16 compute requested but the "
                    "layer is bf16-ineligible (host callback / IO — see "
                    "proto/netshape.py BF16_INELIGIBLE); it will compute "
                    "in f32. Pin `forward_type: FLOAT` to silence.",
                    lp.name, lp.type)
            if lp.type in ("Data", "ImageData", "Input") and batch_divisor > 1:
                # copy-on-write: the NetParameter is often SHARED between
                # the train net (divided) and test nets / the caller's
                # object — in-place division would leak across phases
                lp = copy.deepcopy(lp)
                self._divide_batch(lp, batch_divisor)
            layer = create_layer(lp, policy, phase)
            layer.model_dir = model_dir  # base for any layer-level file paths
            if lp.type in ("Data", "HDF5Data"):
                probe = data_shape_probe
                if probe is None:
                    # default: open the dataset once to discover shapes
                    # (reference DataLayer reads a sample in LayerSetUp)
                    from .data.feeder import data_shape_probe as _default_probe
                    probe = lambda lp_: _default_probe(lp_, model_dir)
                if lp.type == "Data":
                    layer.bound_shape = probe(lp)
                    layer.allow_device_transform = device_transform is not False
                else:
                    layer.bound_shapes = probe(lp)
            # resolve bottoms
            in_shapes = []
            for b in lp.bottom:
                if b not in self.blob_shapes:
                    raise ValueError(
                        f"layer {lp.name!r}: unknown bottom blob {b!r} "
                        "(layers execute in declaration order)"
                    )
                in_shapes.append(self.blob_shapes[b])
            layer.in_shapes = in_shapes
            out_shapes = layer.setup(in_shapes)
            layer.out_shapes = out_shapes
            if len(out_shapes) != len(lp.top) and lp.type != "Silence":
                raise ValueError(
                    f"layer {lp.name!r}: produces {len(out_shapes)} tops, "
                    f"prototxt names {len(lp.top)}"
                )
            for t, s in zip(lp.top, out_shapes):
                if t in self.blob_shapes and t not in lp.bottom:
                    raise ValueError(f"duplicate top blob {t!r} (layer {lp.name!r})")
                self.blob_shapes[t] = tuple(s)
            if isinstance(layer, InputLayerBase):
                self.feed_blobs.extend(lp.top)
                for key, shape, kind in layer.feed_specs():
                    self.feed_specs[key] = (tuple(shape), kind)
            # loss weights (reference layer.hpp SetLossWeights)
            for ti, t in enumerate(lp.top):
                w = (lp.loss_weight[ti] if ti < len(lp.loss_weight)
                     else layer.default_loss_weight(ti))
                if w:
                    self.loss_blobs.append((t, w))
                    self._loss_at[t] = len(self.layers)
            # param sharing bookkeeping
            for pname, decl in layer.params.items():
                key = (lp.name, pname)
                if decl.shared_name:
                    owner = self._shared_owner.get(decl.shared_name)
                    if owner is None:
                        self._shared_owner[decl.shared_name] = key
                    else:
                        owner_layer = self._layer_by_name(owner[0])
                        if owner_layer.params[owner[1]].shape != decl.shape:
                            raise ValueError(
                                f"shared param {decl.shared_name!r}: shape "
                                f"mismatch {decl.shape} vs "
                                f"{owner_layer.params[owner[1]].shape}"
                            )
                        self.param_aliases[key] = owner
            self.layers.append(layer)

        dups = len(self.feed_blobs) - len(set(self.feed_blobs))
        if dups:
            raise ValueError("duplicate feed blob names")
        self.debug_info = bool(param.debug_info)
        self._log_memory()

    def _log_memory(self) -> None:
        """Init-time memory accounting (reference net.cpp:386-400 logs
        top/bottom/param bytes). Estimates: activation blobs at their
        compute dtype + params at master dtype. XLA's actual buffer
        assignment is usually smaller (fusion elides intermediates)."""
        import math

        def nbytes(shape, itemsize=4):
            return math.prod(shape) * itemsize if shape else itemsize

        act = sum(nbytes(s) for s in self.blob_shapes.values())
        par = sum(math.prod(d.shape) * 4
                  for _, _, d in self.learnable_param_decls())
        log.info("Net %s (%s): %d layers, %d blobs (~%.1f MiB activations), "
                 "%d learnable params (%.1f MiB); upper bounds — XLA fuses "
                 "and elides intermediates",
                 self.name or "<unnamed>", self.phase, len(self.layers),
                 len(self.blob_shapes), act / 2**20,
                 self.num_learnable_params(), par / 2**20)

    # ------------------------------------------------------------------
    def _divide_batch(self, lp, divisor: int) -> None:
        """Split a prototxt GLOBAL batch into per-replica/micro batches
        (reference divide_batch_size, parallel.cpp:295-348). Indivisible
        batches RAISE instead of rounding up with a warning: a rounded
        micro-batch silently changes the effective global batch — and so
        the optimization trajectory — which under `-gpipe` the user never
        asked for (the reference's round-up applies to its DP replica
        case, parallel.cpp:284-293, where the feed is re-striped; here
        the micro-batches ARE the accumulation schedule)."""
        if lp.type == "Input":
            # Input nets (synthetic / deploy): the leading dim of every
            # declared shape is the batch — divide it like a data layer's
            # batch_size (gpipe micro-batching reaches here)
            ip = lp.input_param
            if ip:
                for shape in ip.shape:
                    if shape.dim:
                        b = shape.dim[0]
                        if b % divisor:
                            self._reject_indivisible(lp, b, divisor)
                        shape.dim[0] = max(1, b // divisor)
            return
        p = lp.data_param if lp.type == "Data" else lp.image_data_param
        if p and p.batch_size:
            if p.batch_size % divisor:
                self._reject_indivisible(lp, p.batch_size, divisor)
            p.batch_size = max(1, p.batch_size // divisor)

    @staticmethod
    def _reject_indivisible(lp, batch: int, divisor: int):
        micro = (batch + divisor - 1) // divisor
        raise ValueError(
            f"layer {lp.name!r}: global batch {batch} is not divisible by "
            f"{divisor} (micro-batches x replicas); rounding up would "
            f"train at an effective global batch of {micro * divisor}, "
            f"not the configured {batch}. Use a divisible batch_size or "
            f"adjust -gpipe/-gpipe_micro.")

    def bind_mesh(self, mesh_plan) -> None:
        """Hand every layer the active MeshPlan (reference analogue: the
        Caffe singleton's solver_count/rank TLS that layers consult;
        common.hpp:298-544). Layers with distributed execution modes —
        Attention sequence_parallel, Pipeline — specialize their traced
        computation on it; all others ignore it."""
        for layer in self.layers:
            layer.mesh_plan = mesh_plan

    def _layer_by_name(self, name: str) -> Layer:
        # built lazily: callers run both during Init (partial layer list)
        # and after; an O(n) scan inside the build loop made net
        # construction O(n^2) (inception_v3 has ~350 layers)
        idx = self._layer_index
        for i in range(self._indexed_upto, len(self.layers)):
            idx.setdefault(self.layers[i].name, self.layers[i])
        self._indexed_upto = len(self.layers)
        try:
            return idx[name]
        except KeyError:
            raise KeyError(name) from None

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> tuple[Params, State]:
        """Initialize params/state. Shared params are stored once (under the
        owning layer) — aliases resolve at apply time, mirroring the
        reference's learnable-param ownership (net.cpp AppendParam)."""
        params: Params = {}
        state: State = {}
        # host seconds: the fillers dispatch asynchronously, so what the
        # device still owes when the phase closes is in no phase
        with spans.phase("net/fill", layers=len(self.layers)) as filled:
            for i, layer in enumerate(self.layers):
                lkey = jax.random.fold_in(key, i)
                p = {}
                inited = layer.init_params(lkey)
                for pname, arr in inited.items():
                    if (layer.name, pname) in self.param_aliases:
                        continue  # owner holds it
                    p[pname] = arr
                if p:
                    params[layer.name] = p
                s = layer.init_state()
                if s:
                    state[layer.name] = s
            filled.stats["parameters"] = sum(
                a.size for p in params.values() for a in p.values())
        return params, state

    def _layer_params(self, layer: Layer, params: Params, train: bool) -> dict:
        out = {}
        for pname, decl in layer.params.items():
            owner = self.param_aliases.get((layer.name, pname), (layer.name, pname))
            arr = params[owner[0]][owner[1]]
            if train and decl.lr_mult == 0.0:
                # frozen: reference's backward-need analysis skips grad
                # computation (net.cpp:285-360); stop_gradient lets XLA DCE it
                arr = jax.lax.stop_gradient(arr)
            out[pname] = arr
        return out

    # ------------------------------------------------------------------
    def apply(self, params: Params, state: State, feeds: dict[str, jax.Array],
              *, train: bool, rng: jax.Array | None = None
              ) -> tuple[dict[str, jax.Array], State, jax.Array]:
        """Run the graph. Returns (all named blobs, new state, total loss)."""
        return self.apply_range(params, state, feeds, {},
                                0, len(self.layers), train=train, rng=rng)

    def apply_range(self, params: Params, state: State,
                    feeds: dict[str, jax.Array], env: dict[str, jax.Array],
                    lo: int, hi: int, *, train: bool,
                    rng: jax.Array | None = None
                    ) -> tuple[dict[str, jax.Array], State, jax.Array]:
        """Run layers [lo, hi) — the pipeline-stage primitive.

        `env` seeds the blob environment with boundary activations produced
        by earlier layers; `feeds` serves any InputLayerBase in the range.
        Returns (env including this range's tops, updated state, the loss
        contribution of loss blobs PRODUCED in this range). apply() is the
        full-range case, so stage execution and whole-net execution share
        one code path — heterogeneous pipeline parallelism (parallel/
        gpipe.py) is exact vs sequential by construction. RNG folding uses
        the ABSOLUTE layer index, so per-layer streams are identical no
        matter how the net is partitioned."""
        env = dict(env)
        new_state: State = dict(state)
        for i in range(lo, hi):
            layer = self.layers[i]
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            lparams = self._layer_params(layer, params, train)
            lstate = state.get(layer.name, {})
            if isinstance(layer, InputLayerBase):
                bottoms = layer.gather_feeds(feeds)
            else:
                bottoms = [env[b] for b in layer.lp.bottom]
                # per-bottom gradient blocking (LayerParameter.propagate_down;
                # reference net.cpp backward-need analysis honors it)
                if layer.lp.propagate_down:
                    bottoms = [
                        jax.lax.stop_gradient(b)
                        if i < len(layer.lp.propagate_down)
                        and not layer.lp.propagate_down[i] else b
                        for i, b in enumerate(bottoms)
                    ]
            apply_fn = layer.apply
            with layer_scope(layer):
                if layer.lp.remat and train:
                    # recompute this layer's forward during backward
                    # instead of keeping its activations in HBM
                    # (layer-level remat), all but what the layer's type
                    # names as read by its backward pass
                    # (`Layer.kept_under_remat`): the flash kernel's
                    # output, a Mamba2 layer's input product and scan
                    apply_fn = jax.checkpoint(
                        lambda p, s, b, layer=layer, lrng=lrng: layer.apply(
                            p, s, b, train=True, rng=lrng),
                        policy=jax.checkpoint_policies.save_only_these_names(
                            *layer.kept_under_remat))
                    tops, lstate_new = apply_fn(lparams, lstate, bottoms)
                else:
                    tops, lstate_new = apply_fn(lparams, lstate, bottoms,
                                                train=train, rng=lrng)
            if lstate_new is not lstate and lstate_new:
                new_state[layer.name] = lstate_new
            for t, v in zip(layer.lp.top, tops):
                env[t] = v
                if self.debug_info and hasattr(v, "ndim") and v.ndim:
                    # reference debug_info: per-blob mean |activation|
                    # (net.cpp:915-938), printed from inside the compiled step
                    jax.debug.print(
                        "    [Forward] Layer " + layer.name + ", top blob "
                        + t + " data: {m}",
                        m=jnp.mean(jnp.abs(v.astype(jnp.float32))))
        loss = jnp.zeros((), jnp.float32)
        for blob, w in self.loss_blobs:
            if not lo <= self._loss_at[blob] < hi:
                continue  # produced outside this range (another stage)
            contrib = env[blob].astype(jnp.float32)
            loss = loss + w * jnp.sum(contrib)
        return env, new_state, loss

    # ------------------------------------------------------------------
    def forward(self, params: Params, state: State, feeds: dict[str, jax.Array],
                *, rng=None):
        """Inference-style forward (reference Net::Forward)."""
        return self.apply(params, state, feeds, train=False, rng=rng)

    # -- introspection (pycaffe parity helpers) -------------------------
    def learnable_param_decls(self):
        """Yield (layer_name, param_name, decl) for each OWNED param, in
        declaration order — the analogue of Net::learnable_params()."""
        for layer in self.layers:
            for pname, decl in layer.params.items():
                if (layer.name, pname) in self.param_aliases:
                    continue
                yield layer.name, pname, decl

    def num_learnable_params(self) -> int:
        return sum(1 for _ in self.learnable_param_decls())

    # -- .caffemodel interop (reference net.cpp:1055-1248) ----------------
    def export_weights(self, params: Params, state: State
                       ) -> dict[str, list]:
        """Params/state -> {layer_name: positional blob list} in the
        reference's blobs_ order (Net::ToProto)."""
        import numpy as np

        from .parallel.mesh import to_host_array

        def to_host(a):
            # TP weights in multi-host runs span non-addressable devices;
            # to_host_array gathers them (collective — snapshot enters on
            # all ranks and gates only the file writes on rank 0)
            return to_host_array(a, np.float32)

        out: dict[str, list] = {}
        for layer in self.layers:
            blobs = []
            for kind, pname in layer.caffe_blobs():
                if kind == "param":
                    owner = self.param_aliases.get((layer.name, pname),
                                                   (layer.name, pname))
                    blobs.append(to_host(params[owner[0]][owner[1]]))
                elif kind == "state":
                    blobs.append(to_host(state[layer.name][pname]))
                elif kind == "correction":
                    blobs.append(np.ones((1,), np.float32))
            if blobs:
                out[layer.name] = blobs
        return out

    def import_weights(self, params: Params, state: State,
                       weights: dict[str, list], strict: bool = False
                       ) -> tuple[Params, State]:
        """Load by layer-name matching (Net::CopyTrainedLayersFrom:
        unmatched layers keep their initialization unless strict)."""
        import numpy as np
        import jax.numpy as jnp
        params = {k: dict(v) for k, v in params.items()}
        state = {k: dict(v) for k, v in state.items()}
        matched = set()
        for layer in self.layers:
            blobs = weights.get(layer.name)
            if blobs is None:
                continue
            matched.add(layer.name)
            spec = layer.caffe_blobs()
            if len(blobs) != len(spec):
                # tolerate BN scale_bias mismatch: 3 vs 5 blobs
                spec = spec[: len(blobs)]
            correction = 1.0
            for (kind, pname), blob in zip(spec, blobs):
                if kind == "correction":
                    # caffemodel blobs arrive as host ndarrays from the
                    # lint: ok(host-sync) — parser; import is load-time
                    c = float(np.asarray(blob).reshape(-1)[0])
                    # BVLC stores mean/var pre-scaled by the correction;
                    # scale_factor = (c == 0 ? 0 : 1/c) — a zero correction
                    # zeroes the running stats (batch_norm_layer.cpp)
                    correction = 0.0 if c == 0.0 else (1.0 / c)
            for (kind, pname), blob in zip(spec, blobs):
                # lint: ok(host-sync) — load-time weight import, host data
                blob = np.asarray(blob, np.float32)
                if kind == "param":
                    owner = self.param_aliases.get((layer.name, pname),
                                                   (layer.name, pname))
                    cur = params[owner[0]][owner[1]]
                    if tuple(cur.shape) != tuple(blob.shape):
                        if blob.size != cur.size:
                            raise ValueError(
                                f"layer {layer.name!r} blob {pname!r}: shape "
                                f"{blob.shape} incompatible with {cur.shape}")
                        blob = blob.reshape(cur.shape)
                    params[owner[0]][owner[1]] = jnp.asarray(blob, cur.dtype)
                elif kind == "state":
                    cur = state[layer.name][pname]
                    state[layer.name][pname] = jnp.asarray(
                        blob.reshape(cur.shape) * correction, cur.dtype)
        if strict:
            missing = {l.name for l in self.layers if l.params} - matched
            if missing:
                raise ValueError(f"no weights for layers: {sorted(missing)}")
        return params, state
