"""Fused, device-fed, asynchronous evaluation (ISSUE 2).

The contract under test: the fused eval pipeline is an EXECUTION-
SCHEDULE change, not a semantics change. One jitted `lax.scan` over a
[T, B, ...] test super-batch (accumulators carried in HBM, chunks
chained through the program's acc0 input) must produce scores BITWISE
equal on the CPU backend to the classic one-dispatch-per-test-batch
loop it replaces, across: direct test_all calls, in-training boundaries
(including test_initialization), multiple test nets with different
test_iter, snapshot/resume across a test boundary, mesh-sharded (SPMD)
eval feeds, and the gpipe stage-0 eval path. Dispatch accounting: a
pass over test_iter batches costs <= ceil(test_iter/T) + 1 device
dispatches (the +1 is the shared-param on-device copy that decouples
eval from the donating train step).
"""

import logging
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver

CLS_NET = """
name: "cls"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 8 dim: 6 } shape { dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "x" top: "y"
        inner_product_param { num_output: 3
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" include { phase: TRAIN } }
layer { name: "acc" type: "Accuracy" bottom: "y" bottom: "t"
        top: "acc" include { phase: TEST } }
"""

# second test topology: TWO output blobs (loss + accuracy) in TEST phase
CLS_NET_LOSS_ACC = CLS_NET.replace(
    'top: "l" include { phase: TRAIN } }', 'top: "l" }')

BASE = ('base_lr: 0.2 lr_policy: "fixed" max_iter: 1000 type: "SGD" '
        'momentum: 0.9 display: 0 random_seed: 11 ')


def make_solver(extra: str = "", net: str = CLS_NET, test_nets=None, **kw):
    sp = SolverParameter.from_text(BASE + extra)
    sp.net_param = NetParameter.from_text(net)
    if test_nets is not None:
        sp.test_net_param = [NetParameter.from_text(t) for t in test_nets]
    return Solver(sp, **kw)


def cls_feed(seed_base: int = 0, batch: int = 8):
    """Deterministic separable-cluster batches, a pure function of the
    index (the feed_fn protocol the DeviceFeedQueue relies on)."""
    templates = np.random.RandomState(99).randn(3, 6).astype(np.float32)

    def feed(it):
        r = np.random.RandomState(seed_base + it)
        t = r.randint(0, 3, batch)
        x = templates[t] + 0.1 * r.randn(batch, 6).astype(np.float32)
        return {"x": np.asarray(x, np.float32), "t": t.astype(np.int32)}
    return feed


def classic_scores(solver, ti, feed_fn, iters):
    """The pre-ISSUE-2 evaluation loop, reimplemented verbatim as the
    oracle: one jitted forward per test batch, device-chained adds, one
    host transfer at the end."""
    tnet = solver.test_nets[ti]
    out_blobs = tuple(Solver._output_blobs(tnet))

    @jax.jit
    def fwd(p, s, f):
        blobs = tnet.apply(p, s, f, train=False)[0]
        return jnp.stack([jnp.sum(blobs[b]).astype(jnp.float32)
                          for b in out_blobs])

    tparams = solver._shared_params(tnet)
    tstate = solver.net_state
    acc = None
    for k in range(iters):
        sums = fwd(tparams, tstate, feed_fn(k))
        acc = sums if acc is None else acc + sums
    vals = np.asarray(acc) / iters
    return {b: float(v) for b, v in zip(out_blobs, vals)}


class TestBitwiseEquivalence:
    def test_direct_test_all_matches_classic(self):
        s = make_solver("test_iter: 4")
        train, test = cls_feed(0), cls_feed(5000)
        s.step(3, train)
        scores = s.test_all([test])
        assert s._pending_eval is None  # sync wrapper fully drains
        oracle = classic_scores(s, 0, test, 4)
        assert scores[0] == oracle  # bitwise: dict of exact floats

    def test_multi_chunk_pass_matches_classic(self):
        """ceil(test_iter/T) > 1: the accumulator chains ACROSS eval
        dispatches through acc0 in exactly the classic addition order."""
        s = make_solver("test_iter: 7 test_chunk: 3")
        train, test = cls_feed(0), cls_feed(7000)
        s.step(2, train)
        d0 = s.test_dispatch_count
        scores = s.test_all([test])
        assert scores[0] == classic_scores(s, 0, test, 7)
        # 1 param copy + ceil(7/3) = 3 scan chunks
        assert s.test_dispatch_count - d0 == 4
        assert s.test_dispatch_count - d0 <= math.ceil(7 / 3) + 1

    def test_multiple_test_nets_different_test_iter(self):
        s = make_solver("test_iter: 3 test_iter: 5 test_chunk: 2",
                        test_nets=[CLS_NET, CLS_NET_LOSS_ACC])
        train = cls_feed(0)
        feeds = [cls_feed(5000), cls_feed(6000)]
        s.step(2, train)
        d0, p0 = s.test_dispatch_count, s.test_pass_count
        scores = s.test_all(feeds)
        assert scores[0] == classic_scores(s, 0, feeds[0], 3)
        assert scores[1] == classic_scores(s, 1, feeds[1], 5)
        assert set(scores[1]) == {"l", "acc"}
        assert s.test_pass_count - p0 == 2
        # net0: 1 copy + ceil(3/2)=2; net1: 1 copy + ceil(5/2)=3
        assert s.test_dispatch_count - d0 == 7

    def test_degenerate_test_net(self):
        s = make_solver("test_iter: 0")
        assert s.test_all([cls_feed(1)]) == [{}]


class TestChunkSizing:
    def test_explicit_knob_pins_t(self):
        s = make_solver("test_iter: 6 test_chunk: 4")
        assert s._test_chunk_len(s.test_nets[0], 6) == 4
        assert s._test_chunk_len(s.test_nets[0], 3) == 3  # capped by iters

    def test_auto_t_respects_hbm_budget(self):
        s = make_solver("test_iter: 50")
        tnet = s.test_nets[0]
        # default budget: T limited only by iters and the scan-length cap
        assert s._test_chunk_len(tnet, 50) == 50
        assert s._test_chunk_len(tnet, 500) == 64
        # batch bytes: x [8,6] f32 + t [8] int = 224; a 500-byte budget
        # fits 2 batches per super-batch
        s._TEST_SUPER_BATCH_BYTES = 500
        assert s._test_chunk_len(tnet, 50) == 2


class TestAsyncInTraining:
    def test_boundary_scores_and_iteration_tags(self, caplog):
        """Evaluation at test boundaries (incl. test_initialization at
        iter 0) runs async but logs bitwise-classic scores tagged with
        the iteration they evaluate."""
        cfg = ("test_iter: 2 test_interval: 4 test_initialization: true ")
        a = make_solver(cfg)
        train, test = cls_feed(0), cls_feed(5000)
        with caplog.at_level(logging.INFO, "caffe_mpi_tpu.solver"):
            a.step(8, train, test_feed_fns=[test])
        headers = [r.args for r in caplog.records
                   if r.msg.startswith("Test net #%d, iteration")]
        assert headers == [(0, 0), (0, 4)]
        logged = [r.args for r in caplog.records
                  if r.msg.startswith("    Test net")]
        assert [a_[1] for a_ in logged] == ["acc", "acc"]

        # twin without eval: identical training trajectory, classic
        # scores computed synchronously at the same iterations
        b = make_solver(cfg)
        want = [classic_scores(b, 0, test, 2)["acc"]]
        b.step(4, train)
        want.append(classic_scores(b, 0, test, 2)["acc"])
        assert [a_[2] for a_ in logged] == want  # bitwise

    def test_async_eval_does_not_perturb_training(self):
        """Training dispatches the same programs with and without test
        feeds — so params must be BITWISE identical: the async eval
        copies its param view and never touches train state."""
        cfg = "test_iter: 3 test_interval: 4 test_initialization: false "
        a = make_solver(cfg)
        b = make_solver(cfg)
        train, test = cls_feed(0), cls_feed(5000)
        a.step(8, train, test_feed_fns=[test])
        b.step(8, train)
        assert a.iter == b.iter == 8
        for ln in a.params:
            for pn in a.params[ln]:
                np.testing.assert_array_equal(
                    np.asarray(a.params[ln][pn]), np.asarray(b.params[ln][pn]),
                    err_msg=f"params {ln}/{pn}")
        # both boundaries fired and were harvested inside step()
        assert a.test_pass_count == 1  # boundary at iter 4 only (8 = end)
        assert a._pending_eval is None

    def test_boundary_dispatches_only_first_chunk(self):
        """_start_eval returns after chunk 0: the remaining chunks
        dispatch from _continue_eval between train steps (or at
        harvest), so the boundary stall is one dispatch + the param
        copy, not the pass."""
        s = make_solver("test_iter: 6 test_chunk: 2")
        test = cls_feed(5000)
        d0 = s.test_dispatch_count
        s._start_eval([test])
        entry = s._pending_eval["entries"][0]
        assert entry["next"] == 2  # chunk 0 only
        assert s.test_dispatch_count - d0 == 2  # copy + first scan
        # the worker is assembling chunk 1 (the hint) in the background
        scores = s._harvest_eval()  # drains chunks 1..2, then syncs
        assert s.test_dispatch_count - d0 == 4
        assert scores[0] == classic_scores(s, 0, test, 6)

    def test_continue_eval_dispatches_ready_chunks(self):
        s = make_solver("test_iter: 4 test_chunk: 2")
        test = cls_feed(5000)
        s._start_eval([test])
        entry = s._pending_eval["entries"][0]
        # wait for the hinted chunk-1 assembly, then the non-blocking
        # advance must dispatch it
        entry["queue"]._pending[(2, 2)].result()
        s._continue_eval()
        assert entry["next"] == 4
        scores = s._harvest_eval()
        assert scores[0] == classic_scores(s, 0, test, 4)

    def test_no_prefetch_at_max_iter(self):
        """Training that ENDS on a test boundary must not assemble a
        super-batch nobody will consume."""
        s = make_solver("test_iter: 2 test_interval: 4 "
                        "test_initialization: false")
        s.sp.max_iter = 4
        train, test = cls_feed(0), cls_feed(5000)
        s.step(4, train, test_feed_fns=[test])
        assert s.iter == 4 == s.sp.max_iter
        q = s._test_feed_queues.get(0)
        assert q is None or not q._pending

    def test_eval_stall_is_tracked(self):
        s = make_solver("test_iter: 2 test_interval: 2 "
                        "test_initialization: false")
        train, test = cls_feed(0), cls_feed(5000)
        s.step(4, train, test_feed_fns=[test])
        assert s.test_pass_count == 1
        assert s.eval_stall_ms > 0.0

    def test_snapshot_resume_across_test_boundary(self, tmp_path):
        """snapshot at 6 with a test boundary at 4: resuming must
        continue the uninterrupted trajectory and the post-resume evals
        must match the classic oracle."""
        cfg = ('type: "Adam" test_iter: 2 test_interval: 4 snapshot: 6 '
               'test_initialization: false ')
        train, test = cls_feed(0), cls_feed(5000)
        a = make_solver(cfg)
        a.sp.snapshot_prefix = str(tmp_path / "fe")
        a.step(10, train, test_feed_fns=[test])
        a.wait_snapshots()

        c = make_solver(cfg)
        c.restore(str(tmp_path / "fe_iter_6.solverstate"))
        assert c.iter == 6
        c.step(4, train, test_feed_fns=[test])
        for ln in a.params:
            for pn in a.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(a.params[ln][pn]),
                    np.asarray(c.params[ln][pn]),
                    rtol=1e-6, atol=1e-7, err_msg=f"params {ln}/{pn}")
        scores = c.test_all([test])
        assert scores[0] == classic_scores(c, 0, test, 2)


class TestParallelEval:
    def test_mesh_sharded_eval_matches_single_device(self):
        """SPMD runs now evaluate on all chips: the test super-batch
        shards over 'data' (batch axis 2 of [T, 1, B, ...]) and the
        scores match a meshless twin."""
        from caffe_mpi_tpu.parallel import MeshPlan
        train, test = cls_feed(0), cls_feed(5000)
        a = make_solver("test_iter: 4")
        b = make_solver("test_iter: 4", mesh=MeshPlan.data_parallel())
        a.step(2, train)
        b.step(2, train)
        sa = a.test_all([test])
        sb = b.test_all([test])
        assert sb[0].keys() == sa[0].keys()
        for k in sa[0]:
            assert sb[0][k] == pytest.approx(sa[0][k], rel=1e-5, abs=1e-6)
        # the eval feed queue really placed via the mesh
        assert b._test_feed_queues[0].place is not None
        # feeds were sharded, not replicated (batch 8 divides n_data 8)
        assert not b._warned_unsharded_test

    def test_mesh_indivisible_test_batch_replicates(self):
        """A test batch that doesn't divide the 'data' axis falls back
        to replicated evaluation instead of crashing (the pre-ISSUE-2
        behavior for ALL mesh test feeds)."""
        from caffe_mpi_tpu.parallel import MeshPlan
        net = CLS_NET.replace("dim: 8 dim: 6", "dim: 4 dim: 6") \
                     .replace("shape { dim: 8 }", "shape { dim: 4 }")
        test = cls_feed(5000, batch=4)
        a = make_solver("test_iter: 3", net=net)
        b = make_solver("test_iter: 3", net=net,
                        mesh=MeshPlan.data_parallel())
        sa = a.test_all([test])
        sb = b.test_all([test])
        assert b._warned_unsharded_test
        for k in sa[0]:
            assert sb[0][k] == pytest.approx(sa[0][k], rel=1e-5, abs=1e-6)

    def test_shard_feeds_or_replicate(self):
        from caffe_mpi_tpu.parallel import MeshPlan
        mesh = MeshPlan.data_parallel()
        tree = {"x": np.zeros((2, 1, 8, 6), np.float32)}
        placed, sharded = mesh.shard_feeds_or_replicate(tree, batch_axis=2)
        assert sharded
        assert placed["x"].sharding.spec == jax.sharding.PartitionSpec(
            None, None, "data", None)
        odd = {"x": np.zeros((2, 1, 6, 6), np.float32)}
        placed, sharded = mesh.shard_feeds_or_replicate(odd, batch_axis=2)
        assert not sharded
        assert placed["x"].sharding.spec == jax.sharding.PartitionSpec()

    def test_gpipe_stage0_eval(self):
        """Stage-placed params evaluate whole-net on stage-0's device
        through the same fused pipeline; scores are deterministic and
        match the sequential solver's within the gpipe trajectory
        tolerance."""
        train_full, test = cls_feed(0), cls_feed(5000)
        halves = lambda it: {k: v[4 * (it % 2):4 * (it % 2) + 4]
                             for k, v in train_full(it // 2).items()}
        seq = make_solver("test_iter: 3")
        seq.step(2, train_full)
        gp = make_solver("test_iter: 3", gpipe={"stages": 2, "micro": 2})
        gp.step(2, lambda it: halves(it))
        s1 = gp.test_all([test])
        s2 = gp.test_all([test])
        assert s1 == s2  # deterministic
        ref = seq.test_all([test])
        for k in ref[0]:
            assert s1[0][k] == pytest.approx(ref[0][k], rel=5e-4, abs=1e-5)


class TestCLI:
    def test_test_chunk_flag_parses(self):
        from caffe_mpi_tpu.tools.cli import _parser
        for spelling in ("--test-chunk", "--test_chunk", "-test_chunk"):
            args = _parser().parse_args(
                ["train", "-solver", "s.prototxt", spelling, "3"])
            assert args.test_chunk == 3
        assert _parser().parse_args(
            ["train", "-solver", "s.prototxt"]).test_chunk == 0


class TestFeedQueuePrefetch:
    def test_prefetch_builds_ahead_without_blocking(self):
        from caffe_mpi_tpu.data.feeder import DeviceFeedQueue
        calls = []

        def feed(it):
            calls.append(it)
            return {"x": np.full((4, 3), it, np.float32)}

        q = DeviceFeedQueue(feed)
        try:
            q.prefetch(0, 3)
            q._pending[(0, 3)].result()  # worker built it
            n = len(calls)
            out = q.get(0, 3)  # served from the prefetch, no rebuild
            assert len(calls) == n
            assert out["x"].shape == (3, 1, 4, 3)
            q.prefetch(3, 2)
            q.prefetch(3, 2)  # idempotent
            assert len(q._pending) == 1
        finally:
            q.close()

    def test_boundary_prefetch_warms_test_queue(self):
        """Training toward a test boundary schedules the first eval
        super-batch on the worker before the boundary iteration."""
        s = make_solver("test_iter: 2 test_interval: 3 "
                        "test_initialization: false")
        train, test = cls_feed(0), cls_feed(5000)
        s.step(3, train, test_feed_fns=[test])  # ends AT the boundary
        q = s._test_feed_queues.get(0)
        assert q is not None and (0, 2) in q._pending
        s.step(3, train, test_feed_fns=[test])  # consumes it at iter 3
        assert s.test_pass_count == 1
