"""Pipeline parallelism: SPMD shift-register over a 'stage' mesh axis.

The reference has NO pipeline parallelism (SURVEY §2.7: ForwardFromTo is a
sequential loop on one device, net.cpp:669-682); this module is part of the
beyond-reference distributed story (DP: mesh.py; TP: mesh.py sharding
rules; SP: ops/attention.py; EP: ops/moe.py).

TPU-native design — the canonical GPipe-on-SPMD pattern (the
"jax-ml.github.io/scaling-book" pipelining recipe): stages must be
STRUCTURALLY IDENTICAL (a stack of repeated blocks — the transformer /
deep-MLP case where PP pays off). Stage s's params live on mesh position s
of the stage axis: the stacked param pytree has a leading n_stages dim
sharded over that axis, so each device holds exactly ONE stage's weights —
the model memory is truly partitioned, which is the entire point of PP.

Execution is a shift register under shard_map: at tick t every device
applies its stage to the activation it holds, then `ppermute`s the result
to the next device in the ring, while stage 0 injects microbatch t and
stage S-1 emits a finished microbatch. The ppermute traffic is
neighbor-only, so it rides the ICI ring, and XLA's latency-hiding
scheduler overlaps the transfer of tick t with the compute of tick t+1 —
the overlap the reference builds with threads, done by the compiler.

Microbatch I/O is sharded over the stage axis too (GSPMD-paper style):
device s owns microbatches {t : t mod S == s}, and two auxiliary one-slot
registers ride the same ring — an INPUT register rotating toward stage 0
(so stage 0 receives microbatch t exactly at tick t) and an OUTPUT
register rotating away from stage S-1 (so each finished microbatch lands
back on its owner). Per-device memory is n_micro/S microbatches + O(1)
registers; per-tick traffic is 3 neighbor ppermutes of one microbatch.
Nothing is replicated and there is no final psum.

Differentiation: plain jax.grad through the scan — AD reverses the
ppermute ring automatically, producing the reverse-direction gradient
pipeline without any hand-written backward schedule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import mark_varying


def stack_stage_params(per_stage_params: list):
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim.
    Every stage must have congruent treedef/shapes (structural identity)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def shard_stages(stacked_params, mesh, stage_axis: str = "model"):
    """Place the stacked params with the leading (stage) dim sharded over
    the stage axis — one stage per mesh position, model memory 1/S per
    device."""
    def put(x):
        spec = [stage_axis] + [None] * (x.ndim - 1)
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return jax.tree.map(put, stacked_params)


def _arrange(mb, n_stages, n_local):
    """(M, ...) microbatch-major -> (S*L, ...) device-major round-robin:
    row s*L + k holds microbatch k*S + s, so a P(stage) split gives device
    s exactly the microbatches {t : t mod S == s} in slot order."""
    rest = mb.shape[1:]
    return (mb.reshape(n_local, n_stages, *rest)
            .swapaxes(0, 1)
            .reshape(n_stages * n_local, *rest))


def _unarrange(out, n_stages, n_local):
    """Inverse of _arrange on the output side."""
    rest = out.shape[1:]
    return (out.reshape(n_stages, n_local, *rest)
            .swapaxes(0, 1)
            .reshape(n_stages * n_local, *rest))


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, *,
                   stage_axis: str = "model", batch_axis: str | None = None):
    """Run a homogeneous stage stack as a pipelined SPMD program.

    stage_fn(stage_params, x) -> y        one stage, pure, shape-preserving
    stacked_params                        leading dim = n_stages (sharded
                                          or not; sharding constraint is
                                          applied here)
    microbatches: (n_micro, ...)          microbatch-major input
    batch_axis: optional mesh axis the per-microbatch batch dim (dim 1) is
    sharded over — pass 'data' when running inside a DPxPP step so the
    shard_map does not force an all-gather of the data-parallel batch.

    Returns (n_micro, ...) outputs equal to applying the stages
    sequentially to each microbatch.
    """
    n_stages = mesh.shape[stage_axis]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != n_stages:
        raise ValueError(
            f"stacked params have {lead} stages but the '{stage_axis}' "
            f"mesh axis has {n_stages} positions")
    n_micro0 = microbatches.shape[0]
    if n_micro0 < 1:
        raise ValueError("need at least one microbatch")

    # pad the microbatch count up to a multiple of S so the round-robin
    # ownership is uniform; pad outputs are sliced off below
    pad = (-n_micro0) % n_stages
    if pad:
        microbatches = jnp.concatenate(
            [microbatches,
         jnp.zeros((pad, *microbatches.shape[1:]), microbatches.dtype)])
    n_micro = n_micro0 + pad
    n_local = n_micro // n_stages

    param_specs = jax.tree.map(
        lambda x: P(*([stage_axis] + [None] * (x.ndim - 1))), stacked_params)
    mb_ndim = microbatches.ndim
    io_spec = P(*([stage_axis, batch_axis] + [None] * (mb_ndim - 2))
                if batch_axis else [stage_axis] + [None] * (mb_ndim - 1))

    def spmd(params, mb_local):
        # params: this device's stage (leading dim 1) — unstack it
        p = jax.tree.map(lambda x: x[0], params)
        idx = lax.axis_index(stage_axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        fwd = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        bwd = [(j, (j - 1) % n_stages) for j in range(n_stages)]

        mb_local = mark_varying(mb_local, stage_axis)
        zero = jnp.zeros_like(mb_local[0])
        in_reg0 = mark_varying(zero, stage_axis)
        state0 = mark_varying(zero, stage_axis)
        out_reg0 = mark_varying(zero, stage_axis)
        out_local0 = mark_varying(jnp.zeros_like(mb_local), stage_axis)

        def tick(carry, t):
            in_reg, state, out_reg, out_local = carry
            # 1. register store: a finished microbatch emitted by stage S-1
            #    ((S-1-idx+... ) ticks ago, riding the output register)
            #    reaches its owner this tick
            d_store = t - (n_stages - 1) - ((idx + 1) % n_stages)
            store = ((idx != n_stages - 1) & (d_store >= 0)
                     & (d_store < n_micro) & (d_store % n_stages == idx))
            slot = jnp.clip(d_store // n_stages, 0, n_local - 1)
            out_local = jnp.where(
                store,
                lax.dynamic_update_index_in_dim(out_local, out_reg, slot, 0),
                out_local)
            # 2. load phase: every S ticks each device refills its input
            #    register from its local shard; the register then rotates
            #    toward stage 0, delivering microbatch t at tick t
            k = t // n_stages
            load = (t % n_stages == 0) & (k < n_local)
            in_reg = jnp.where(
                load,
                lax.dynamic_index_in_dim(
                    mb_local, jnp.minimum(k, n_local - 1), 0, keepdims=False),
                in_reg)
            # 3. inject + compute
            x = jnp.where(is_first, in_reg, state)
            y = stage_fn(p, x)
            # 4. emission: stage S-1 finished microbatch t-(S-1); microbatches
            #    it owns itself store directly, the rest board the register
            d_emit = t - (n_stages - 1)
            self_store = (is_last & (d_emit >= 0) & (d_emit < n_micro)
                          & (d_emit % n_stages == n_stages - 1))
            out_local = jnp.where(
                self_store,
                lax.dynamic_update_index_in_dim(
                    out_local, y, jnp.clip(d_emit // n_stages, 0,
                                           n_local - 1), 0),
                out_local)
            out_reg = jnp.where(is_last, y, out_reg)
            # 5. ring rotations (neighbor-only ICI traffic)
            state = lax.ppermute(y, stage_axis, fwd)
            in_reg = lax.ppermute(in_reg, stage_axis, bwd)
            out_reg = lax.ppermute(out_reg, stage_axis, fwd)
            return (in_reg, state, out_reg, out_local), None

        n_ticks = n_micro + 2 * n_stages - 2
        (_, _, _, out_local), _ = lax.scan(
            tick, (in_reg0, state0, out_reg0, out_local0),
            jnp.arange(n_ticks))
        return out_local

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(param_specs, io_spec),  # microbatch I/O sharded over stage
        out_specs=io_spec,
    )
    out = fn(stacked_params, _arrange(microbatches, n_stages, n_local))
    out = _unarrange(out, n_stages, n_local)
    return out[:n_micro0] if pad else out
