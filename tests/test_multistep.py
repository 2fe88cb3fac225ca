"""The train loop's host-visible schedule: one `jit_step` dispatch an
iteration, display lines, test passes and snapshots at their iterations,
a resumed run on the uninterrupted trajectory, `step()`'s return value;
plus the device feed queue fused evaluation reads from.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver

LSQ_NET = """
name: "lsq"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 4 dim: 3 } shape { dim: 4 dim: 1 } } }
layer { name: "ip" type: "InnerProduct" bottom: "x" top: "pred"
        inner_product_param { num_output: 1
          weight_filler { type: "gaussian" std: 1 }
          bias_filler { type: "gaussian" std: 1 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "pred" bottom: "t" top: "l" }
"""

CLS_NET = """
name: "cls"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 8 dim: 6 } shape { dim: 8 } } }
layer { name: "ip" type: "InnerProduct" bottom: "x" top: "y"
        inner_product_param { num_output: 3
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" }
"""


def make_solver(extra: str = "", net: str = LSQ_NET) -> Solver:
    sp = SolverParameter.from_text(
        f'base_lr: 0.1 max_iter: 1000 lr_policy: "fixed" display: 0 '
        f'random_seed: 7\n{extra}')
    sp.net_param = NetParameter.from_text(net)
    return Solver(sp)


def lsq_data(rng, n=32):
    out = []
    for _ in range(n):
        x = rng.randn(4, 3).astype(np.float32)
        t = (x @ np.array([[1.0], [-2.0], [0.5]]) + 0.3).astype(np.float32)
        out.append({"x": x, "t": t})
    return out


def assert_same_training(a: Solver, b: Solver, rtol=1e-5, atol=1e-6):
    assert a.iter == b.iter
    for ln in a.params:
        for pn in a.params[ln]:
            np.testing.assert_allclose(
                np.asarray(a.params[ln][pn]), np.asarray(b.params[ln][pn]),
                rtol=rtol, atol=atol, err_msg=f"params {ln}/{pn}")
    for ln in a.opt_state:
        for pn in a.opt_state[ln]:
            for si, (sa, sb) in enumerate(zip(a.opt_state[ln][pn],
                                              b.opt_state[ln][pn])):
                np.testing.assert_allclose(
                    np.asarray(sa), np.asarray(sb), rtol=rtol, atol=atol,
                    err_msg=f"opt {ln}/{pn}[{si}]")


class TestEventBoundaries:
    """Display, test and snapshot events fire at the iterations the
    reference's Solver::Step puts them, one dispatch an iteration."""

    def test_display_parity(self, rng, caplog):
        """Display lines appear after iterations 0, 5, 10 and 15, each
        with the mean of the last `average_loss` iterations' losses."""
        import logging
        data = lsq_data(rng)
        cfg = 'type: "SGD" momentum: 0.9 display: 5 average_loss: 3'
        s = make_solver(cfg)
        with caplog.at_level(logging.INFO, "caffe_mpi_tpu.solver"):
            caplog.clear()
            s.step(17, lambda it: data[it % 32])
        shown = [(r.args[0], float(r.args[3]))  # (iteration, smoothed loss)
                 for r in caplog.records if "Iteration" in r.msg]
        assert [i for i, _ in shown] == [0, 5, 10, 15]
        assert s.dispatch_count == 17
        assert s.host_sync_count == 4
        # each iteration's own loss, from a twin stepped one at a time
        twin = make_solver(cfg.replace("display: 5", "display: 0"))
        losses = [twin.step(1, lambda it: data[it % 32]) for _ in range(17)]
        for i, smoothed in shown:
            window = losses[max(0, i - 2):i + 1]
            assert smoothed == pytest.approx(sum(window) / len(window),
                                             rel=1e-5), f"iter {i}"

    def test_test_interval_parity(self, rng):
        """Test passes start before iterations 6, 12 and 18."""
        data = lsq_data(rng)
        cfg = ('type: "SGD" momentum: 0.9 test_interval: 6 test_iter: 2 '
               'test_initialization: false')
        s = make_solver(cfg, net=LSQ_NET)
        fired = []
        # the in-training boundary dispatches the ASYNC eval
        # entrypoint (ISSUE 2); hook it to observe firing iterations
        orig = s._start_eval
        s._start_eval = lambda fns: fired.append(s.iter) or orig(fns)
        s.step(20, lambda it: data[it % 32],
               test_feed_fns=[lambda k: data[(7 + k) % 32]])
        assert fired == [6, 12, 18]
        assert s.dispatch_count == 20

    def test_test_interval_without_feeds_does_not_clip(self, rng):
        """A configured-but-unused test_interval (no test feeds passed to
        step()) starts no test pass and dispatches nothing but the
        iterations."""
        data = lsq_data(rng)
        s = make_solver('type: "SGD" momentum: 0.9 test_interval: 2')
        s.step(20, lambda it: data[it % 32])  # no test_feed_fns
        assert s.dispatch_count == 20
        assert s.test_pass_count == 0 and s.test_dispatch_count == 0

    def test_snapshot_boundary_and_resume(self, rng, tmp_path):
        """Interval snapshots land after iterations 5 and 11, and a
        resume from the iteration-6 state continues on the uninterrupted
        trajectory."""
        data = lsq_data(rng)
        cfg = 'type: "Adam" momentum: 0.9 snapshot: 6 average_loss: 2'
        a = make_solver(cfg)
        a.sp.snapshot_prefix = str(tmp_path / "a")
        a.step(14, lambda it: data[it % 32])
        a.wait_snapshots()
        for it in (6, 12):
            assert (tmp_path / f"a_iter_{it}.caffemodel").exists()
            assert (tmp_path / f"a_iter_{it}.solverstate").exists()
        c = make_solver(cfg)
        # prefix pinned to tmp: the resumed run crosses the iter-12
        # snapshot boundary, and the default prefix litters the repo root
        c.sp.snapshot_prefix = str(tmp_path / "resume")
        c.restore(str(tmp_path / "a_iter_6.solverstate"))
        assert c.iter == 6
        c.step(8, lambda it: data[it % 32])
        c.wait_snapshots()
        assert (tmp_path / "resume_iter_12.caffemodel").exists()
        assert_same_training(a, c)

    def test_solver_step_returns_final_loss(self, rng):
        """step(n) returns the loss of its last iteration."""
        data = lsq_data(rng)
        a = make_solver('type: "SGD" momentum: 0.9')
        b = make_solver('type: "SGD" momentum: 0.9')
        la = a.step(11, lambda it: data[it % 32])
        b.step(10, lambda it: data[it % 32])
        lb = b.step(1, lambda it: data[it % 32])
        assert la == lb == float(a._loss_window[-1])


class TestDeviceFeedQueue:
    def test_stack_shape_and_prefetch(self):
        from caffe_mpi_tpu.data.feeder import DeviceFeedQueue
        calls = []

        def feed(it):
            calls.append(it)
            return {"x": np.full((4, 3), it, np.float32),
                    "t": np.full((4,), it, np.int32)}

        q = DeviceFeedQueue(feed)
        try:
            out = q.get(0, 3, hint=(3, 2))
            assert out["x"].shape == (3, 1, 4, 3)
            assert out["t"].shape == (3, 1, 4)
            # batch j of the super-batch is feed_fn(it0 + j)
            got = np.asarray(out["x"])[:, :, 0, 0]
            np.testing.assert_array_equal(got, [[0], [1], [2]])
            assert np.asarray(out["t"]).dtype == np.int32
            # the hint was prefetched on the worker; get() must not
            # rebuild it
            q._pending[(3, 2)].result()
            n_before = len(calls)
            out2 = q.get(3, 2)
            assert len(calls) == n_before  # served from prefetch
            np.testing.assert_array_equal(
                np.asarray(out2["x"])[:, :, 0, 0], [[3], [4]])
        finally:
            q.close()

    def test_device_resident_leaves(self):
        """jnp-array feeds (synthetic benches) stack on device without a
        host round-trip path error."""
        from caffe_mpi_tpu.data.feeder import DeviceFeedQueue
        feeds = {"x": jnp.ones((4, 3))}
        q = DeviceFeedQueue(lambda it: feeds)
        try:
            out = q.get(5, 2)
            assert out["x"].shape == (2, 1, 4, 3)
            assert isinstance(out["x"], jax.Array)
        finally:
            q.close()

    def test_build_error_propagates(self):
        from caffe_mpi_tpu.data.feeder import DeviceFeedQueue

        def bad(it):
            raise RuntimeError("boom")

        q = DeviceFeedQueue(bad)
        try:
            with pytest.raises(RuntimeError, match="boom"):
                q.get(0, 2)
        finally:
            q.close()


class TestSatellites:
    def test_divide_batch_indivisible_raises(self):
        """gpipe micro-batching of an indivisible global batch must fail
        loudly, naming the effective batch — not silently round up."""
        sp = SolverParameter.from_text(
            'base_lr: 0.1 max_iter: 10 lr_policy: "fixed"')
        sp.net_param = NetParameter.from_text(LSQ_NET)  # batch 4
        with pytest.raises(ValueError, match="effective global batch"):
            Solver(sp, gpipe={"stages": 1, "micro": 3})

    def test_synthetic_feeds_structural_labels(self):
        """Integer feeds chosen by CONSUMER structure (1-D bottom of a
        classification loss), not the literal blob name 'label'."""
        from caffe_mpi_tpu.utils.model_shapes import (input_shapes,
                                                      synthetic_feeds)
        npar = NetParameter.from_text(CLS_NET)  # label top named "t"
        shapes = input_shapes(npar, batch=8)
        feeds = synthetic_feeds(shapes, n_classes=3, npar=npar)
        assert jnp.issubdtype(feeds["t"].dtype, jnp.integer)
        assert feeds["t"].shape == (8,)
        assert int(feeds["t"].max()) < 3
        assert jnp.issubdtype(feeds["x"].dtype, jnp.floating)
        # without a net to inspect, 1-D tops still get integer feeds
        feeds2 = synthetic_feeds(shapes, n_classes=3)
        assert jnp.issubdtype(feeds2["t"].dtype, jnp.integer)

    def test_gpipe_clip_no_host_sync(self, rng):
        """gpipe clip_gradients computes the clip scale as a device
        scalar (ADVICE r5) and still matches the sequential trajectory."""
        net = LSQ_NET.replace("dim: 4 dim: 3", "dim: 8 dim: 3") \
                     .replace("dim: 4 dim: 1", "dim: 8 dim: 1")
        data = []
        for _ in range(16):
            x = rng.randn(8, 3).astype(np.float32)
            t = (x @ np.array([[1.0], [-2.0], [0.5]]) + 0.3).astype(np.float32)
            data.append({"x": x, "t": t})
        halves = [{k: v[4 * m:4 * (m + 1)] for k, v in d.items()}
                  for d in data for m in range(2)]
        cfg = 'type: "SGD" momentum: 0.9 clip_gradients: 0.05'
        seq = make_solver(cfg, net=net)
        seq.step(6, lambda it: data[it])
        sp = SolverParameter.from_text(
            f'base_lr: 0.1 max_iter: 1000 lr_policy: "fixed" display: 0 '
            f'random_seed: 7\n{cfg}')
        sp.net_param = NetParameter.from_text(net)
        gp = Solver(sp, gpipe={"stages": 2, "micro": 2})
        gp.step(6, lambda it: halves[it])
        assert_same_training(seq, gp, rtol=1e-4, atol=1e-5)
