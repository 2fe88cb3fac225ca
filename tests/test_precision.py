"""Mixed-precision bf16 training (ISSUE 9): the `precision` solver knob.

Four contracts, mirroring the reference's fp16 system (caffe.proto
forward_type/backward_type + solver_data_type, net.cpp:815-818 loss
scaling) as rebuilt for TPU:

1. The f32 path is UNTOUCHED: a solver that spells `precision: "f32"`
   (+ loss-scale knobs, which are bf16-only) trains bitwise-identically
   to one that predates the knob, across step_chunk {1,K} x train_guard
   x reduce_overlap.
2. Under `precision: bf16`, activations/gradients compute in bfloat16
   while params and momentum stay f32 MASTER copies updated in f32 —
   held against a torch-amp-style oracle (torch is the independent
   numerical oracle of this suite, CLAUDE.md).
3. Dynamic loss scaling (loss_scale 0) composes with the train guard: a
   fault-injected overflow becomes skip + scale-down (+ regrowth after
   loss_scale_window clean steps) instead of the exit-88 divergence
   policy, which still fires for f32 guard runs and for bf16 once the
   scale floor is reached.
4. reduce_overlap buckets pack and psum in bf16 (collective bytes
   halve) and serving's bucket programs run bf16 within tolerance of
   f32 at zero extra compiles.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from caffe_mpi_tpu.proto import NetParameter, SolverParameter
from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.utils import resilience

NET = """
name: "prec_net"
layer { name: "in" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 16 dim: 1 dim: 8 dim: 8 }
                      shape { dim: 16 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 4 kernel_size: 3
          weight_filler { type: "msra" } } }
layer { name: "r" type: "ReLU" bottom: "c" top: "c" }
layer { name: "ip" type: "InnerProduct" bottom: "c" top: "logits"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
        bottom: "label" top: "loss" }
"""


def _feed(rng_seed=0):
    r = np.random.RandomState(rng_seed)
    batches = [{"data": jnp.asarray(r.randn(16, 1, 8, 8).astype(np.float32)),
                "label": jnp.asarray(r.randint(0, 4, 16))}
               for _ in range(24)]
    return lambda it: batches[it % len(batches)]


def _solver(extra="", net=NET, **kw):
    sp = SolverParameter.from_text(
        'base_lr: 0.05 momentum: 0.9 lr_policy: "fixed" max_iter: 100 '
        'random_seed: 3 '
        # tmp prefix: the exit-88 path journals <prefix>.run.json — a
        # bare default would litter the repo root on every suite run
        'snapshot_prefix: "/tmp/caffe_tpu_precision/snap" ' + extra)
    sp.net_param = NetParameter.from_text(net)
    return Solver(sp, **kw)


def _params_host(s):
    return {ln: {pn: np.asarray(a) for pn, a in lp.items()}
            for ln, lp in s.params.items()}


def _assert_trees_equal(a, b):
    for ln in a:
        for pn in a[ln]:
            np.testing.assert_array_equal(
                a[ln][pn], b[ln][pn], err_msg=f"{ln}/{pn} differs")


class TestF32Bitwise:
    """Spelling the knobs at their f32 defaults must not move a bit."""

    @pytest.mark.parametrize("variant", ["plain", "chunk", "guard",
                                         "chunk_guard"])
    def test_f32_knob_is_bitwise_noop(self, variant):
        extra = {"plain": "",
                 "chunk": "step_chunk: 3",
                 "guard": "train_guard: true",
                 "chunk_guard": "step_chunk: 3 train_guard: true"}[variant]
        base = _solver(extra)
        base.step(7, _feed())
        knob = _solver(extra + ' precision: "f32" loss_scale: 128 '
                       'loss_scale_window: 7')
        knob.step(7, _feed())
        _assert_trees_equal(_params_host(base), _params_host(knob))

    def test_f32_reduce_overlap_bitwise_noop(self):
        from caffe_mpi_tpu.parallel import MeshPlan
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")
        base = _solver("reduce_overlap: true", mesh=MeshPlan.data_parallel())
        assert base._reduction is not None, base._reduction_fallback
        base.step(5, _feed())
        knob = _solver('reduce_overlap: true precision: "f32" '
                       'loss_scale: 64', mesh=MeshPlan.data_parallel())
        knob.step(5, _feed())
        _assert_trees_equal(_params_host(base), _params_host(knob))


LINEAR_NET = """
name: "lin"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 8 dim: 16 } shape { dim: 8 dim: 4 } } }
layer { name: "fc" type: "InnerProduct" bottom: "x" top: "y"
        inner_product_param { num_output: 4 bias_term: false
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "y" bottom: "t"
        top: "l" }
"""


class TestBF16MasterWeights:
    def test_master_update_matches_torch_amp_oracle(self):
        torch = pytest.importorskip("torch")
        r = np.random.RandomState(0)
        x = r.randn(8, 16).astype(np.float32)
        t = r.randn(8, 4).astype(np.float32)

        s = _solver('precision: "bf16" loss_scale: 1024', net=LINEAR_NET,
                    )
        sp_lr = 0.05
        w0 = np.asarray(s.params["fc"]["weight"])  # (4, 16) f32 master
        assert s.params["fc"]["weight"].dtype == jnp.float32
        s.step(1, lambda it: {"x": jnp.asarray(x), "t": jnp.asarray(t)})
        assert s.params["fc"]["weight"].dtype == jnp.float32
        w1 = np.asarray(s.params["fc"]["weight"])

        # torch-amp-style oracle: bf16 forward off the f32 master, f32
        # loss, STATIC loss scale applied and unwound exactly like
        # net.cpp:815-818, SGD+momentum update applied to the f32 master
        wt = torch.tensor(w0, requires_grad=True)
        y = torch.tensor(x).bfloat16() @ wt.bfloat16().T
        loss = ((y.float() - torch.tensor(t).bfloat16().float())
                ** 2).sum() / (2 * 8)
        (loss * 1024.0).backward()
        g = wt.grad.float() / 1024.0
        w_ref = torch.tensor(w0) - sp_lr * g  # first step: momentum 0
        np.testing.assert_allclose(w1, w_ref.numpy(), rtol=2e-2,
                                   atol=2e-4)
        assert np.abs(w1 - w0).max() > 0

    def test_updates_land_in_f32_below_bf16_resolution(self):
        # an update smaller than one bf16 ulp of the weight must still
        # move the f32 master — the whole point of master weights
        s = _solver('precision: "bf16" loss_scale: 1', net=LINEAR_NET)
        s.sp.base_lr = 1e-6
        r = np.random.RandomState(1)
        feed = lambda it: {"x": jnp.asarray(r.randn(8, 16).astype(np.float32)),
                           "t": jnp.asarray(r.randn(8, 4).astype(np.float32))}
        w0 = np.asarray(s.params["fc"]["weight"])
        s.step(1, feed)
        w1 = np.asarray(s.params["fc"]["weight"])
        delta = w1 - w0
        moved = delta != 0
        assert moved.sum() > delta.size // 2
        # the same update applied to a bf16 copy of the master and
        # rounded back to bf16 is lost on every weight: the bf16 spacing
        # at each w0 is found by rounding, not by |w0| * 2^-8 (a bound
        # relative to |w0| that no update meets for a weight near zero)
        bf16 = lambda a: np.asarray(
            jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))
        w0_bf16 = bf16(w0)
        assert np.array_equal(bf16(w0_bf16 + delta)[moved], w0_bf16[moved])

    def test_activations_bf16_loss_f32(self):
        s = _solver('precision: "bf16" loss_scale: 2')
        feeds = _feed()(0)
        blobs, _, loss = s.net.apply(s.params, s.net_state, feeds,
                                     train=True, rng=jax.random.PRNGKey(0))
        assert blobs["c"].dtype == jnp.bfloat16
        assert blobs["logits"].dtype == jnp.bfloat16
        assert loss.dtype == jnp.float32
        # momentum slots stay f32
        assert all(sl.dtype == jnp.float32
                   for lp in s.opt_state.values()
                   for slots in lp.values() for sl in slots)

    def test_bf16_converges_with_dynamic_scaling(self):
        r = np.random.RandomState(2)
        templates = r.randn(4, 1, 8, 8).astype(np.float32)

        def feed(it):
            rr = np.random.RandomState(it)
            lab = rr.randint(0, 4, 16)
            return {"data": jnp.asarray(
                templates[lab] + 0.1 * rr.randn(16, 1, 8, 8).astype(
                    np.float32)),
                "label": jnp.asarray(lab)}

        s = _solver('precision: "bf16" step_chunk: 5')
        assert s._dyn_scale and s._guard_on
        l0 = s.step(5, feed)
        lN = s.step(35, feed)
        assert lN < 0.5 * l0
        assert s.overflow_steps == 0


class TestDynamicLossScale:
    def _burst_feed(self, bad_iters):
        clean = _feed(5)
        nan = {"data": jnp.asarray(np.full((16, 1, 8, 8), np.nan,
                                           np.float32)),
               "label": jnp.asarray(np.zeros(16, np.int64))}
        return lambda it: nan if it in bad_iters else clean(it)

    def test_overflow_skips_and_rescales_instead_of_exit88(self):
        s = _solver('precision: "bf16" guard_max_skips: 2 '
                    'loss_scale_window: 4')
        s.step(9, self._burst_feed({3, 4, 5}))  # burst > guard_max_skips
        assert s.skipped_steps == 3
        assert s.overflow_steps == 3
        assert s.loss_scale_value == 2.0 ** 15 / 8  # three halvings
        # clean window -> regrowth: 3 clean steps already banked after
        # the burst, 11 more = three window-4 growth events, back to the
        # 2^15 start
        s.step(11, self._burst_feed(set()))
        assert s.loss_scale_value == 2.0 ** 15
        assert s.skipped_steps == 3

    def test_f32_guard_same_burst_exits_88(self):
        s = _solver("train_guard: true guard_max_skips: 2")
        with pytest.raises(resilience.NumericAnomalyError):
            s.step(9, self._burst_feed({3, 4, 5}))

    def test_fault_injected_overflow_recovers(self):
        # the ISSUE 4 fault plane injects the NaNs (range-keyed feed
        # poisoning) — the acceptance-criteria spelling of the burst
        s = _solver('precision: "bf16" guard_max_skips: 2')
        resilience.FAULTS.configure("nan_grad:2:0:3")  # iters 3,4 bad
        try:
            s.step(8, _feed(7))
        finally:
            resilience.FAULTS.configure("")
        assert s.skipped_steps == 2
        assert s.overflow_steps == 2
        assert s.loss_scale_value == 2.0 ** 15 / 4

    def test_scale_floor_still_trips_divergence_policy(self):
        # a run that is ACTUALLY divergent (every step non-finite)
        # halves to the floor and then the exit-88 policy fires — the
        # self-healing contract survives under bf16
        s = _solver('precision: "bf16" guard_max_skips: 2 '
                    'step_chunk: 5')
        bad = self._burst_feed(set(range(100)))
        with pytest.raises(resilience.NumericAnomalyError):
            s.step(30, bad)

    def test_finite_spike_skips_without_touching_scale(self):
        # review finding (ISSUE 9): a guard_loss_spike skip on a FINITE
        # loss is a real anomaly, not an overflow — it must not halve
        # the loss scale, must not count as an overflow, and must feed
        # the guard_max_skips divergence counter immediately (no
        # waiting for the scale floor)
        s = _solver('precision: "bf16" guard_loss_spike: 3.0 '
                    'guard_max_skips: 2')
        clean = _feed(5)
        spike = {"data": clean(0)["data"] * 60.0,
                 "label": jnp.asarray((np.asarray(clean(0)["label"]) + 2)
                                      % 4)}
        s.step(6, clean)  # build the accepted-loss EMA
        assert s.skipped_steps == 0
        scale0, ov0 = s.loss_scale_value, s.overflow_steps
        s.step(1, lambda it: spike)
        assert s.skipped_steps == 1          # the spike was skipped...
        assert s.overflow_steps == ov0       # ...but is NOT an overflow
        assert s.loss_scale_value == scale0  # and the scale is untouched
        # two consecutive finite spikes trip the divergence policy even
        # though the scale never reached its floor
        with pytest.raises(resilience.NumericAnomalyError):
            s.step(2, lambda it: spike)

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="precision"):
            _solver('precision: "fp8"')
        with pytest.raises(ValueError, match="loss_scale"):
            _solver('precision: "bf16" loss_scale: -1')
        with pytest.raises(ValueError, match="loss_scale_window"):
            _solver('precision: "bf16" loss_scale_window: 0')
        with pytest.raises(ValueError, match="gpipe"):
            _solver('precision: "bf16"', gpipe=2)


class TestBF16Reduction:
    def test_bucket_bytes_halve_and_training_runs(self):
        from caffe_mpi_tpu.parallel import MeshPlan
        if len(jax.devices()) < 2:
            pytest.skip("needs >1 device")
        f32 = _solver("reduce_overlap: true", mesh=MeshPlan.data_parallel())
        b16 = _solver('reduce_overlap: true precision: "bf16"',
                      mesh=MeshPlan.data_parallel())
        sf, sb = f32.reduction_stats(), b16.reduction_stats()
        assert sf["mode"] == "bucketed" and sb["mode"] == "bucketed"
        assert sb["wire_dtype"] == "bfloat16"
        assert "wire_dtype" not in sf
        assert sum(sb["bucket_bytes"]) * 2 == sum(sf["bucket_bytes"])
        loss = b16.step(4, _feed())
        assert np.isfinite(loss)
        assert b16.params["conv"]["weight"].dtype == jnp.float32

    def test_bf16_fused_eval_runs(self):
        sp = SolverParameter.from_text(
            'base_lr: 0.05 max_iter: 20 precision: "bf16" test_iter: 4 '
            'test_interval: 10 test_initialization: false test_chunk: 2')
        sp.net_param = NetParameter.from_text(NET)
        s = Solver(sp)
        scores = s.test_all([_feed(9)])
        assert scores and np.isfinite(scores[0]["loss"])


LRN_NET = """
name: "lrn_net"
layer { name: "in" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 4 dim: 8 dim: 6 dim: 6 }
                      shape { dim: 4 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 8 kernel_size: 3 pad: 1
          weight_filler { type: "msra" } } }
layer { name: "norm" type: "LRN" bottom: "c" top: "n"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "ip" type: "InnerProduct" bottom: "n" top: "logits"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
        bottom: "label" top: "loss" }
"""


class TestPallasLRN:
    """ops/lrn.py wired behind the precision policy (ISSUE 9)."""

    def _feed(self):
        r = np.random.RandomState(4)
        return {"data": jnp.asarray(r.randn(4, 8, 6, 6).astype(np.float32)),
                "label": jnp.asarray(r.randint(0, 4, 4))}

    # (N, C, H, W), local_size, ops/lrn.py _BLOCK_BYTES (None = as
    # shipped; small values, counted as VMEM tiles the block, force
    # several blocks with a ragged last one)
    KERNEL_CASES = {
        # batch on the lanes: the (H*W, C, N) view
        "lanes-batch128-c16": ((128, 16, 3, 5), 5, None),
        "lanes-batch256-c96": ((256, 96, 2, 3), 5, None),
        "lanes-batch128-c5-size3": ((128, 5, 2, 2), 3, None),
        "lanes-batch128-c3-below-window": ((128, 3, 2, 2), 5, None),
        "lanes-batch128-ragged-rows": ((128, 5, 7, 1), 5, 3 * 32 * 128),
        "lanes-batch256-two-lane-blocks": ((256, 16, 3, 3), 5,
                                           16 * 128 * 2),
        # spatial on the lanes: the (N, C, H*W) view
        "spatial-batch2-c16": ((2, 16, 7, 9), 5, None),
        "spatial-batch5-c96-size3": ((5, 96, 3, 3), 3, None),
        "spatial-batch2-c5": ((2, 5, 4, 4), 5, None),
        "spatial-batch2-c3-below-window": ((2, 3, 4, 4), 5, None),
        "spatial-batch2-short-last-tile": ((2, 8, 15, 20), 5, None),
        "spatial-batch5-ragged-rows": ((5, 16, 6, 6), 3, 2 * 16 * 128 * 4),
        "spatial-batch2-ragged-lanes": ((2, 16, 15, 20), 5, 16 * 128 * 4),
    }

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", KERNEL_CASES)
    def test_kernel_matches_lax_fwd_and_bwd(self, case, dtype, monkeypatch):
        from jax import lax
        from caffe_mpi_tpu.ops import lrn
        shape, size, block_bytes = self.KERNEL_CASES[case]
        if block_bytes is not None:
            monkeypatch.setattr(lrn, "_BLOCK_BYTES", block_bytes)
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(*shape).astype(np.float32) * 2, dtype)
        dy = jnp.asarray(r.randn(*shape).astype(np.float32), dtype)

        def ref(x, alpha=1e-4, beta=0.75, k=1.0):
            half = (size - 1) // 2
            ws = lax.reduce_window(
                jnp.square(x), np.zeros((), np.dtype(x.dtype))[()],
                lax.add, window_dimensions=(1, size, 1, 1),
                window_strides=(1, 1, 1, 1),
                padding=((0, 0), (half, half), (0, 0), (0, 0)))
            return x * jnp.power(k + ws * (alpha / size), -beta)

        y, vjp = jax.vjp(
            lambda x: lrn.lrn_across_channels(x, size, 1e-4, 0.75, 1.0), x)
        dx, = vjp(dy)
        assert y.dtype == dx.dtype == x.dtype
        # the reference in f32 from the same inputs; bf16 I/O rounds the
        # kernel's f32 result once at the block's edge
        y_ref, vjp_ref = jax.vjp(ref, x.astype(jnp.float32))
        dx_ref, = vjp_ref(dy.astype(jnp.float32))
        fwd, bwd = ((dict(rtol=1e-5, atol=1e-6), dict(rtol=1e-4, atol=1e-5))
                    if dtype == "float32" else
                    (dict(rtol=1e-2, atol=1e-3),) * 2)
        np.testing.assert_allclose(y.astype(jnp.float32), y_ref, **fwd)
        np.testing.assert_allclose(dx.astype(jnp.float32), dx_ref, **bwd)

    def test_bf16_routes_through_pallas_f32_does_not(self, monkeypatch):
        monkeypatch.delenv("CAFFE_LRN_PALLAS", raising=False)
        for precision, expect_pallas in (("bf16", True), ("", False)):
            s = _solver('precision: "bf16"' if precision else "",
                        net=LRN_NET)
            jaxpr = jax.make_jaxpr(
                lambda p, st, f: s.net.apply(p, st, f, train=True,
                                             rng=jax.random.PRNGKey(0)))(
                s.params, s.net_state, self._feed())
            has_pallas = "pallas" in str(jaxpr)
            assert has_pallas == expect_pallas, (precision, has_pallas)
        # CAFFE_LRN_PALLAS=0 opts the bf16 path back out
        monkeypatch.setenv("CAFFE_LRN_PALLAS", "0")
        s = _solver('precision: "bf16"', net=LRN_NET)
        jaxpr = jax.make_jaxpr(
            lambda p, st, f: s.net.apply(p, st, f, train=True,
                                         rng=jax.random.PRNGKey(0)))(
            s.params, s.net_state, self._feed())
        assert "pallas" not in str(jaxpr)

    def test_bf16_lrn_net_trains(self, monkeypatch):
        monkeypatch.delenv("CAFFE_LRN_PALLAS", raising=False)
        s = _solver('precision: "bf16" step_chunk: 3', net=LRN_NET)
        loss = s.step(6, lambda it: self._feed())
        assert np.isfinite(loss)
        assert s.skipped_steps == 0

    def test_forced_pallas_matches_stock_f32_training(self, monkeypatch):
        # CAFFE_LRN_PALLAS=1: the kernels under the plain f32 path must
        # track the stock lax program to f32 tolerance over real steps
        monkeypatch.setenv("CAFFE_LRN_PALLAS", "0")
        a = _solver("", net=LRN_NET)
        a.step(4, lambda it: self._feed())
        monkeypatch.setenv("CAFFE_LRN_PALLAS", "1")
        b = _solver("", net=LRN_NET)
        b.step(4, lambda it: self._feed())
        for ln in a.params:
            for pn in a.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(a.params[ln][pn]),
                    np.asarray(b.params[ln][pn]), rtol=1e-4, atol=1e-6,
                    err_msg=f"{ln}/{pn}")


class TestBF16Serving:
    def _deploy(self, tmp_path):
        text = """
name: "srv"
layer { name: "in" type: "Input" top: "data"
        input_param { shape { dim: 4 dim: 1 dim: 8 dim: 8 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 4 kernel_size: 3
          weight_filler { type: "msra" } } }
layer { name: "ip" type: "InnerProduct" bottom: "c" top: "logits"
        inner_product_param { num_output: 3
          weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "logits" top: "prob" }
"""
        p = tmp_path / "deploy.prototxt"
        p.write_text(text)
        return str(p)

    def test_scores_close_and_zero_extra_compiles(self, tmp_path):
        from caffe_mpi_tpu.serving.engine import BucketedForward
        path = self._deploy(tmp_path)
        param = NetParameter.from_file(path)
        f32 = BucketedForward(param, ladder=(1, 4))
        b16 = BucketedForward(param, ladder=(1, 4), dtype="bf16")
        params, state = f32.init(seed=0)
        f32.warm(params, state)
        b16.warm(params, state)
        assert f32.counter.count == 2 and b16.counter.count == 2
        r = np.random.RandomState(0)
        for n in (1, 3, 4, 2):  # mixed arrival sizes
            data = r.randn(n, 1, 8, 8).astype(np.float32)
            sf = f32.forward(params, state, data)
            sb = b16.forward(params, state, data)
            assert sf.dtype == np.float32 and sb.dtype == np.float32
            np.testing.assert_allclose(sb, sf, rtol=5e-2, atol=5e-3)
        # steady state compiled nothing new on either path
        assert f32.counter.count == 2 and b16.counter.count == 2

    def test_engine_serve_dtype_knob(self, tmp_path):
        from caffe_mpi_tpu.proto.config import ServingParameter
        from caffe_mpi_tpu.serving import ServingEngine
        path = self._deploy(tmp_path)
        spp = ServingParameter()
        spp.serve_dtype = "bf16"
        eng = ServingEngine(spp, start=False)
        try:
            eng.load_model("m", path)
            assert eng.compile_count == eng.warmed_buckets
        finally:
            eng.close()
        with pytest.raises(ValueError, match="serve_dtype"):
            spp2 = ServingParameter()
            spp2.serve_dtype = "fp8"
            ServingEngine(spp2, start=False)
