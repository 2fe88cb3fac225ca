"""One program an iteration on the plain path (`Solver.step` at chunk
length 1): the feed goes into the step as `feed_fn` returned it, leaves
(B, ...), unless `iter_size` stacks it; the iteration's key is folded
from the base key inside the program, and the counter goes in as a host
scalar. Held here: the losses are bitwise those of the formulation before
(the stack and `fold_in` on the host, written out below as this file's
own oracle), a batch already on the device is touched by no program but
`step`, a mesh shards the unstacked batch on axis 0, and `step_hlo_text`
lowers the same arguments."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffe_mpi_tpu.parallel import MeshPlan
from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.utils import compile_cache

NET = """
name: "drop_mlp"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 12 dim: 10 } shape { dim: 12 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
        inner_product_param { num_output: 24
          weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "h" top: "h" }
layer { name: "drop" type: "Dropout" bottom: "h" top: "h"
        dropout_param { dropout_ratio: 0.5 } }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "y"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" }
"""


def make_solver(extra: str = "", mesh=None) -> Solver:
    sp = SolverParameter.from_text(
        f'base_lr: 0.05 momentum: 0.9 lr_policy: "step" stepsize: 2 '
        f'gamma: 0.5 max_iter: 100 display: 0 random_seed: 11\n{extra}')
    sp.net_param = NetParameter.from_text(NET)
    return Solver(sp, mesh=mesh)


def batches(n: int, seed: int = 5, on_device: bool = True) -> list[dict]:
    r = np.random.RandomState(seed)
    out = [{"x": r.randn(12, 10).astype(np.float32),
            "t": r.randint(0, 4, 12).astype(np.int32)} for _ in range(n)]
    return jax.tree.map(jnp.asarray, out) if on_device else out


def formulation_before(solver: Solver, data: list[dict], n: int):
    """The plain path as it was launched before: every leaf stacked on a
    leading iter_size axis and the iteration's key folded on the host,
    both handed to the iteration body; returns the n losses and the
    parameters."""
    body = jax.jit(solver._iteration_fn())
    iter_size = max(solver.sp.iter_size, 1)
    state = (solver.params, solver.net_state, solver.opt_state)
    gstate = (solver._guard_state0(),) if solver._guard_on else ()
    losses = []
    for it in range(n):
        stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *(data[it * iter_size + k] for k in range(iter_size)))
        rng = jax.random.fold_in(solver.base_rng, it + 1)
        out = body(*state, stack, jnp.int32(it), rng, *gstate)
        state, loss, gstate = out[:3], out[3], out[5:]
        losses.append(float(loss))
    return losses, state[0]


# -- (a) bit for bit what it was ---------------------------------------------

@pytest.mark.parametrize("extra", ["", "train_guard: true",
                                   'precision: "bf16"'],
                         ids=["plain", "guard", "bf16"])
@pytest.mark.parametrize("iter_size", [1, 2])
def test_losses_are_bitwise_those_of_the_formulation_before(iter_size,
                                                            extra):
    extra = f"iter_size: {iter_size}\n{extra}"
    data = batches(4 * iter_size)
    oracle = make_solver(extra)
    solver = make_solver(extra)
    try:
        want, want_params = formulation_before(oracle, data, 4)
        got = [solver.step(1, lambda it: data[it]) for _ in range(4)]
        assert got == want      # dropout masks and the schedule included
        assert len(set(got)) == 4 and all(np.isfinite(got))
        for a, b in zip(jax.tree.leaves(solver.params),
                        jax.tree.leaves(want_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        oracle.close()
        solver.close()


def test_a_host_feed_gives_the_losses_of_a_device_feed():
    on_host, on_device = batches(3, on_device=False), batches(3)
    assert isinstance(on_host[0]["x"], np.ndarray)
    a, b = make_solver(), make_solver()
    try:
        assert [a.step(1, lambda it: on_host[it]) for _ in range(3)] \
            == [b.step(1, lambda it: on_device[it]) for _ in range(3)]
    finally:
        a.close()
        b.close()


# -- (b) the programs of an iteration ----------------------------------------

@pytest.fixture
def programs(monkeypatch):
    """A fresh row store under the start-up ledger's listeners."""
    monkeypatch.setattr(compile_cache, "programs",
                        compile_cache.ProgramLedger())
    compile_cache.install_ledger()
    return compile_cache.programs


@pytest.mark.parametrize("extra,mesh", [
    ("", False), ("train_guard: true", False), ("", True),
    ("display: 1 average_loss: 2", False)],     # the display's read too
    ids=["plain", "guard", "dp4", "display"])
def test_a_device_feed_is_touched_by_no_program_but_step(programs, extra,
                                                         mesh):
    plan = MeshPlan.data_parallel(jax.devices()[:4]) if mesh else None
    solver = make_solver(extra, mesh=plan)
    data = batches(3)
    if plan is not None:
        data = [plan.shard_feeds(d) for d in data]
    handed = []

    def feed_fn(it):
        handed.append(data[it])
        return data[it]
    try:
        if solver._guard_on:    # the guard's first carry: once a run
            solver._gstate = solver._guard_state0()
        jax.block_until_ready((solver.params, data))
        jax.clear_caches()      # an eager program met before is built anew
        programs.rows.clear()
        programs.events.clear()
        solver.step(3, feed_fn)
        jax.block_until_ready(solver.params)
    finally:
        solver.close()
    built = {name: row.built for name, row in programs.rows.items()
             if row.built}
    # no `broadcast_in_dim` over the batch, no `_threefry_fold_in`, no cast
    assert built == {"step": 1}, built
    assert len(handed) == 3 and solver.dispatch_count == 3


def test_the_feed_argument_is_the_array_the_feed_returned():
    solver = make_solver()
    try:
        (batch,) = batches(1)
        placed = solver._place_feeds([batch])
        assert placed["x"] is batch["x"] and placed["t"] is batch["t"]
        stacked = make_solver("iter_size: 2")
        try:
            two = stacked._place_feeds([batch, batch])
        finally:
            stacked.close()
        assert two["x"].shape == (2, 12, 10) and two["t"].shape == (2, 12)
    finally:
        solver.close()


# -- (c) under a mesh ---------------------------------------------------------

@pytest.mark.parametrize("iter_size", [1, 2])
def test_a_mesh_shards_the_batch_axis_and_matches_one_device(iter_size):
    plan = MeshPlan.data_parallel(jax.devices()[:4])
    extra = f"iter_size: {iter_size}"
    data = batches(3 * iter_size, on_device=False)
    one, dp = make_solver(extra), make_solver(extra, mesh=plan)
    try:
        placed = dp._place_feeds(data[:iter_size])
        axis = 0 if iter_size == 1 else 1
        for leaf, shape in ((placed["x"], (12, 10)), (placed["t"], (12,))):
            assert leaf.shape == (iter_size,) * axis + shape
            shards = leaf.addressable_shards
            assert len(shards) == 4
            want = list(leaf.shape)
            want[axis] = 3      # 12 rows over 4 devices, nothing else cut
            assert all(list(s.data.shape) == want for s in shards)
        l1 = one.step(3, lambda it: data[it])
        l2 = dp.step(3, lambda it: data[it])
        assert l1 == pytest.approx(l2, rel=1e-4)
        for a, b in zip(jax.tree.leaves(one.params),
                        jax.tree.leaves(dp.params)):
            assert b.sharding.is_fully_replicated
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)
    finally:
        one.close()
        dp.close()


# -- (d) the lowering surface -------------------------------------------------

@pytest.mark.parametrize("mesh", [False, True], ids=["one", "dp4"])
@pytest.mark.parametrize("iter_size", [1, 2])
def test_step_hlo_text_lowers_what_the_step_takes(iter_size, mesh):
    plan = MeshPlan.data_parallel(jax.devices()[:4]) if mesh else None
    solver = make_solver(f"iter_size: {iter_size}", mesh=plan)
    try:
        text = solver.step_hlo_text(batches(1, on_device=False)[0])
    finally:
        solver.close()
    entry = text[text.index("ENTRY"):]
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", entry)
    rows = 3 if mesh else 12     # a device's share of the batch
    lead = f"{iter_size}," if iter_size > 1 else ""
    assert f"f32[{lead}{rows},10]" in params, params
    assert f"s32[{lead}{rows}]" in params, params
    # the counter and the base key are arguments; the fold is in the program
    assert "s32[]" in params and "u32[2]" in params
    if iter_size > 1:
        assert " while(" in text
    assert bool(re.search(r" all-reduce(-start)?\(", text)) == mesh
