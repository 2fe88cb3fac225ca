"""Pipeline layer — prototxt surface for pipeline parallelism.

TPU-native extension with no reference analogue (SURVEY §2.7: the
reference's ForwardFromTo is a sequential one-device loop,
net.cpp:669-682; PP is absent). This layer makes parallel/pipeline.py's
GPipe-on-SPMD schedule reachable from the model definition, the way every
reference capability is reachable from a prototxt:

  layer {
    name: "trunk" type: "Pipeline" bottom: "h" top: "h_out"
    pipeline_param {
      num_stages: 4 micro_batches: 8
      layer { name: "ln"   type: "LayerNorm"    bottom: "h" top: "n" ... }
      layer { name: "attn" type: "Attention"    bottom: "n" top: "a" ... }
      layer { name: "res"  type: "Eltwise"      bottom: "h" bottom: "a"
              top: "h" }
    }
  }

The inner `layer {...}` sub-graph defines ONE block; the Pipeline layer is
`num_stages` structurally identical copies of it chained head-to-tail
(each stage has its OWN weights, initialized independently). Params are
stored STACKED with a leading stage dim — under a mesh whose 'model' axis
equals num_stages the Solver shards that dim so each device holds exactly
one stage (see Solver._prototxt_shardings), and apply() runs the
shift-register pipeline schedule with the batch split into
`micro_batches`. On a single device the same stacked params run as a
sequential lax.scan over stages — identical math, so the two execution
modes are exact-match testable against each other.

Constraints (checked at setup): the block must be shape-preserving
(output shape == input shape, so stages chain), single-input
single-output, and stateless (no BatchNorm running stats — which also
rules out the one op whose batch statistics would make microbatch
splitting inexact). Dropout inside a block is rejected in TRAIN phase:
the schedule applies stages under scan/shard_map where a per-layer rng
stream is not yet threaded.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
from jax import lax

log = logging.getLogger(__name__)

from ..core.fillers import fill
from ..utils.spans import layer_scope
from .base import Layer, ParamDecl, Shape, create_layer, register


@register("Pipeline")
class PipelineLayer(Layer):
    def setup(self, in_shapes: list[Shape]) -> list[Shape]:
        p = self.lp.pipeline_param
        if p is None or p.num_stages < 1 or not p.layer:
            raise ValueError(
                f"layer {self.name!r}: pipeline_param needs num_stages >= 1 "
                "and at least one inner layer")
        if len(self.lp.bottom) != 1:
            raise ValueError(
                f"layer {self.name!r}: Pipeline takes exactly one bottom")
        self.p = p
        self.n_stages = p.num_stages
        self.n_micro = max(p.micro_batches, 1)
        in_shape = tuple(in_shapes[0])
        if in_shape[0] % self.n_micro:
            raise ValueError(
                f"layer {self.name!r}: batch {in_shape[0]} not divisible by "
                f"micro_batches {self.n_micro}")

        # build ONE block's layers; shapes chained through a local env
        self.block: list[Layer] = []
        self.block_input = self.lp.bottom[0]
        env = {self.block_input: in_shape}
        if self.n_micro % self.n_stages:
            # pipeline_apply pads the microbatch count up to a multiple of
            # num_stages and discards the pad results — legal, but the pad
            # microbatches cost full stage compute
            log.warning(
                "layer %s: micro_batches %d is not a multiple of num_stages "
                "%d; the pipelined schedule pads to %d and %d of them are "
                "wasted compute", self.name, self.n_micro, self.n_stages,
                -(-self.n_micro // self.n_stages) * self.n_stages,
                (-self.n_micro) % self.n_stages)
        for ilp in p.layer:
            if ilp.type == "Dropout" and self.phase == "TRAIN":
                raise ValueError(
                    f"layer {self.name!r}: Dropout inside a Pipeline block "
                    "is unsupported in TRAIN phase (no per-stage rng stream)")
            if (ilp.attention_param is not None
                    and ilp.attention_param.sequence_parallel):
                raise ValueError(
                    f"pipeline block layer {ilp.name!r}: sequence_parallel "
                    "attention inside a Pipeline block is unsupported — the "
                    "stage is already shard_mapped over the 'model' axis, so "
                    "the sequence cannot shard over it too")
            il = create_layer(ilp, self.policy, self.phase)
            shapes = []
            for b in ilp.bottom:
                if b not in env:
                    raise ValueError(
                        f"pipeline block layer {ilp.name!r}: unknown bottom "
                        f"{b!r}")
                shapes.append(env[b])
            il.in_shapes = shapes
            outs = il.setup(shapes)
            il.out_shapes = outs
            if il.init_state():
                raise ValueError(
                    f"pipeline block layer {ilp.name!r} ({ilp.type}) is "
                    "stateful; only stateless ops can be pipelined")
            for t, s in zip(ilp.top, outs):
                env[t] = tuple(s)
            self.block.append(il)
        self.block_output = self.block[-1].lp.top[0]
        out_shape = env[self.block_output]
        if out_shape != in_shape:
            raise ValueError(
                f"layer {self.name!r}: pipeline block must be "
                f"shape-preserving, got {in_shape} -> {out_shape}")

        # stacked param decls: leading stage dim on every inner param;
        # inner lr/decay multipliers carry over
        self._inner_decls: list[tuple[Layer, str, ParamDecl]] = []
        for il in self.block:
            for pname, decl in il.params.items():
                if decl.shared_name:
                    raise ValueError(
                        f"pipeline block layer {il.name!r}: cross-net param "
                        "sharing inside a block is unsupported")
                stacked = ParamDecl(shape=(self.n_stages, *decl.shape),
                                    filler=decl.filler,
                                    lr_mult=decl.lr_mult,
                                    decay_mult=decl.decay_mult,
                                    dtype=decl.dtype)
                self.params[f"{il.name}.{pname}"] = stacked
                self._inner_decls.append((il, pname, decl))
        return [in_shape]

    def init_params(self, key: jax.Array) -> dict[str, jax.Array]:
        """Each stage gets its own independent draw of the block's
        fillers (fan-in/fan-out computed on the UNSTACKED shapes)."""
        out = {}
        for i, (il, pname, decl) in enumerate(self._inner_decls):
            dtype = decl.dtype if decl.dtype is not None else self.policy.master
            stages = [
                fill(decl.filler, jax.random.fold_in(key, i * self.n_stages + s),
                     decl.shape, dtype)
                for s in range(self.n_stages)
            ]
            out[f"{il.name}.{pname}"] = jnp.stack(stages)
        return out

    # ------------------------------------------------------------------
    def _stage_fn(self, train: bool):
        def stage(p_stage, x):
            env = {self.block_input: x}
            for il in self.block:
                lparams = {pn: p_stage[f"{il.name}.{pn}"] for pn in il.params}
                bottoms = [env[b] for b in il.lp.bottom]
                with layer_scope(il):
                    tops, _ = il.apply(lparams, {}, bottoms, train=train,
                                       rng=None)
                for t, v in zip(il.lp.top, tops):
                    env[t] = v
            return env[self.block_output]
        return stage

    def apply(self, params, state, bottoms, *, train, rng):
        x = bottoms[0]
        stage = self._stage_fn(train)
        mp = self.mesh_plan
        pipelined = (mp is not None and self.n_stages > 1
                     and mp.mesh.shape.get("model", 1) == self.n_stages)
        if pipelined:
            from ..parallel.pipeline import pipeline_apply
            n = x.shape[0]
            n_data = mp.mesh.shape.get("data", 1)
            if (n // self.n_micro) % n_data:
                raise ValueError(
                    f"layer {self.name!r}: per-microbatch batch "
                    f"{n // self.n_micro} (batch {n} / micro_batches "
                    f"{self.n_micro}) must divide the mesh 'data' axis "
                    f"({n_data}); raise the Input batch or lower "
                    "micro_batches / the data axis")
            mb = x.reshape(self.n_micro, n // self.n_micro, *x.shape[1:])
            out = pipeline_apply(
                stage, params, mb, mp.mesh, stage_axis="model",
                batch_axis="data" if n_data > 1 else None)
            y = out.reshape(x.shape)
        else:
            # single-device / mismatched mesh: sequential scan over the
            # stage dim of the very same stacked params
            y, _ = lax.scan(lambda h, p_s: (stage(p_s, h), None), x, params)
        return [y], state

    @property
    def kept_under_remat(self) -> tuple[str, ...]:
        """Under the Pipeline layer's own `remat`, what its block's layers
        keep under theirs."""
        return tuple(name for il in self.block for name in il.kept_under_remat)
