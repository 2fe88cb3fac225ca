"""Overlapped bucketed gradient reduction (ISSUE 6,
caffe_mpi_tpu/parallel/reduction.py — reference ReduceAndUpdate,
src/caffe/net.cpp:757-913).

The contract under test: `reduce_overlap` is an EXECUTION-SCHEDULE
knob, not a semantics knob — the shard_map step with per-bucket psums
must land on BITWISE-identical params and optimizer state (CPU
backend) vs the implicit GSPMD reduction, across step_chunk {1, K},
iter_size accumulation, global-norm clipping, and train_guard. Plus:
the bucket planner's ordering/sizing rules, the knob validation that
replaces the old accept-and-ignore, the net-compatibility fallback,
and the per-step collective count the MULTICHIP dryrun reports.
"""

import logging

import numpy as np
import pytest

from caffe_mpi_tpu.parallel import MeshPlan, reduction
from caffe_mpi_tpu.proto import SolverParameter
from caffe_mpi_tpu.proto.config import NetParameter
from caffe_mpi_tpu.solver import Solver

MLP_NET = """
name: "mlp"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 16 dim: 6 } shape { dim: 16 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
        inner_product_param { num_output: 32
          weight_filler { type: "xavier" } } }
layer { name: "r" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "y"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" }
"""

BN_NET = """
name: "bn_net"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 16 dim: 4 dim: 4 dim: 4 }
                      shape { dim: 16 } } }
layer { name: "conv" type: "Convolution" bottom: "x" top: "c"
        convolution_param { num_output: 4 kernel_size: 3 pad: 1
          weight_filler { type: "msra" } } }
layer { name: "bn" type: "BatchNorm" bottom: "c" top: "c" }
layer { name: "ip" type: "InnerProduct" bottom: "c" top: "y"
        inner_product_param { num_output: 4
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" }
"""


def make_solver(extra: str = "", net: str = MLP_NET, mesh=None) -> Solver:
    sp = SolverParameter.from_text(
        f'base_lr: 0.1 momentum: 0.9 max_iter: 1000 lr_policy: "fixed" '
        f'display: 0 random_seed: 5\n{extra}')
    sp.net_param = NetParameter.from_text(net)
    return Solver(sp, mesh=mesh)


def mlp_data(rng, n=32):
    return [{"x": rng.randn(16, 6).astype(np.float32),
             "t": rng.randint(0, 4, 16)} for _ in range(n)]


def assert_bitwise(a: Solver, b: Solver):
    """Params AND optimizer slots must be byte-identical — the
    acceptance bar for the overlapped step on the CPU backend."""
    for ln in a.params:
        for pn in a.params[ln]:
            ea, eb = np.asarray(a.params[ln][pn]), np.asarray(
                b.params[ln][pn])
            assert np.array_equal(ea, eb), \
                f"params {ln}/{pn} differ (max " \
                f"{np.abs(ea - eb).max():.3e})"
    for ln in a.opt_state:
        for pn in a.opt_state[ln]:
            for si, (sa, sb) in enumerate(zip(a.opt_state[ln][pn],
                                              b.opt_state[ln][pn])):
                assert np.array_equal(np.asarray(sa), np.asarray(sb)), \
                    f"opt {ln}/{pn}[{si}] differs"


# ---------------------------------------------------------------------------
# Bucket planner
# ---------------------------------------------------------------------------

class TestPlanner:
    ENTRIES = [  # (layer, param, shape, dtype) already reverse-topo
        ("ip2", "weight", (4, 32), np.float32),   # 512 B
        ("ip2", "bias", (4,), np.float32),        # 16 B
        ("ip1", "weight", (32, 6), np.float32),   # 768 B
        ("ip1", "bias", (32,), np.float32),       # 128 B
    ]

    def test_count_mode_produces_k_contiguous_buckets(self):
        plan = reduction.plan_buckets(self.ENTRIES, n_buckets=3, n_data=8)
        assert len(plan.buckets) == 3
        # contiguity: concatenating the buckets reproduces the order
        flat = [e for b in plan.buckets for e in b.entries]
        assert flat == [(l, p) for (l, p, _, _) in self.ENTRIES]
        assert sum(plan.bucket_bytes) == 512 + 16 + 768 + 128
        assert plan.collectives_per_step == 3

    def test_reverse_topo_order_from_net(self):
        s = make_solver("reduce_overlap: true reduce_buckets: 2",
                        mesh=MeshPlan.data_parallel())
        order = [e[0] for b in s._reduction.buckets for e in b.entries]
        # backward produces ip2's grads before ip1's
        assert order.index("ip2") < order.index("ip1")
        assert set(order) == {"ip1", "ip2"}

    def test_more_buckets_than_params_caps_at_params(self):
        plan = reduction.plan_buckets(self.ENTRIES, n_buckets=64)
        assert len(plan.buckets) == 4  # one per param, never empty ones

    def test_byte_budget_mode(self):
        plan = reduction.plan_buckets(self.ENTRIES, bucket_bytes=600)
        # greedy: [512+16=528], [768 overflows alone], [128]
        assert [b.nbytes for b in plan.buckets] == [528, 768, 128]

    def test_single_oversized_param_gets_own_bucket_and_warns(self, caplog):
        with caplog.at_level(logging.WARNING,
                             "caffe_mpi_tpu.parallel.reduction"):
            plan = reduction.plan_buckets(self.ENTRIES, bucket_bytes=256)
        sizes = [b.nbytes for b in plan.buckets]
        assert 512 in sizes and 768 in sizes  # each oversized, alone
        assert any("exceeds the grad_bucket_mb budget" in r.message
                   for r in caplog.records)

    def test_dtype_change_splits_bucket(self):
        entries = [("a", "w", (8,), np.float32),
                   ("b", "w", (8,), np.float16),
                   ("c", "w", (8,), np.float16)]
        plan = reduction.plan_buckets(entries, n_buckets=1)
        assert [b.dtype for b in plan.buckets] == ["float32", "float16"]

    def test_zero_knobs_rejected(self):
        with pytest.raises(ValueError, match="n_buckets"):
            reduction.plan_buckets(self.ENTRIES)


# ---------------------------------------------------------------------------
# Knob validation — the old silent accept-and-ignore must be gone
# ---------------------------------------------------------------------------

class TestKnobValidation:
    def test_net_level_zero_reduce_buckets_rejected(self):
        sp = SolverParameter.from_text(
            'base_lr: 0.1 max_iter: 10 lr_policy: "fixed"')
        sp.net_param = NetParameter.from_text(
            MLP_NET.replace('name: "mlp"', 'name: "mlp"\nreduce_buckets: 0'))
        with pytest.raises(ValueError, match="reduce_buckets"):
            Solver(sp)

    @pytest.mark.parametrize("knob", ["reduce_buckets: 0",
                                      "reduce_buckets: -2",
                                      "grad_bucket_mb: 0",
                                      "grad_bucket_mb: -1.5"])
    def test_solver_level_zero_or_negative_rejected(self, knob):
        with pytest.raises(ValueError):
            make_solver(knob)

    def test_both_sizing_modes_rejected(self):
        with pytest.raises(ValueError, match="not both"):
            make_solver("reduce_buckets: 4 grad_bucket_mb: 8.0")

    def test_overlap_without_mesh_rejected(self):
        with pytest.raises(ValueError, match="mesh"):
            make_solver("reduce_overlap: true")

    def test_valid_net_level_default_flows_into_plan(self):
        s = make_solver("reduce_overlap: true",
                        mesh=MeshPlan.data_parallel())
        # net-level default (6) caps at the 4 params
        assert 1 <= len(s._reduction.buckets) <= 6
        assert s.reduction_stats()["mode"] == "bucketed"


# ---------------------------------------------------------------------------
# Fallback gate
# ---------------------------------------------------------------------------

class TestFallback:
    def test_batchnorm_net_falls_back_with_warning(self, caplog):
        with caplog.at_level(logging.WARNING, "caffe_mpi_tpu.solver"):
            s = make_solver("reduce_overlap: true", net=BN_NET,
                            mesh=MeshPlan.data_parallel())
        assert s._reduction is None
        stats = s.reduction_stats()
        assert stats["mode"] == "implicit"
        assert "BatchNorm" in stats["fallback_reason"]
        assert any("falling back" in r.message for r in caplog.records)

    def test_fallback_net_still_trains(self, rng):
        s = make_solver("reduce_overlap: true", net=BN_NET,
                        mesh=MeshPlan.data_parallel())
        data = {"x": rng.randn(16, 4, 4, 4).astype(np.float32),
                "t": rng.randint(0, 4, 16)}
        loss = s.step(2, lambda it: data)
        assert np.isfinite(loss)

    def test_ignore_label_valid_norm_falls_back(self):
        net = MLP_NET.replace(
            'bottom: "t"\n        top: "l"',
            'bottom: "t"\n        top: "l"\n'
            '        loss_param { ignore_label: -1 }')
        s = make_solver("reduce_overlap: true", net=net,
                        mesh=MeshPlan.data_parallel())
        assert s._reduction is None
        assert "ignore_label" in s.reduction_stats()["fallback_reason"]

    def test_unsupported_reason_passes_clean_net(self):
        s = make_solver()
        assert reduction.unsupported_reason(s.net) is None

    def test_single_device_data_axis_falls_back(self):
        # the reference's reduce thread is idle at solver_count 1
        # (net.cpp:757-913 never fires) — with one device on the 'data'
        # axis there is nothing to reduce, and falling back keeps the
        # n=1 step bitwise (no all-reduce exists in the implicit
        # program for the clip/guard fusion boundary to differ against)
        import jax
        s = make_solver("reduce_overlap: true",
                        mesh=MeshPlan.from_shape(
                            data=1, devices=jax.devices()[:1]))
        assert s._reduction is None
        assert "single device" in s.reduction_stats()["fallback_reason"]


# ---------------------------------------------------------------------------
# Bitwise equivalence vs the implicit reduction (the acceptance bar)
# ---------------------------------------------------------------------------

class TestEquivalence:
    @pytest.mark.parametrize("cfg", [
        "",
        "clip_gradients: 0.7",
        "step_chunk: 4 clip_gradients: 0.7",
        "step_chunk: 4 train_guard: true clip_gradients: 0.7",
        "iter_size: 2 clip_gradients: 0.7",
        "iter_size: 2 step_chunk: 3 train_guard: true",
    ])
    def test_bitwise_vs_implicit(self, rng, cfg):
        data = mlp_data(rng)
        a = make_solver(cfg, mesh=MeshPlan.data_parallel())
        b = make_solver(cfg + " reduce_overlap: true reduce_buckets: 3",
                        mesh=MeshPlan.data_parallel())
        assert b._reduction is not None, b._reduction_fallback
        a.step(8, lambda it: data[it % 32])
        b.step(8, lambda it: data[it % 32])
        assert_bitwise(a, b)

    def test_byte_budget_plan_matches_too(self, rng):
        data = mlp_data(rng)
        a = make_solver("clip_gradients: 0.5",
                        mesh=MeshPlan.data_parallel())
        b = make_solver("clip_gradients: 0.5 reduce_overlap: true "
                        "grad_bucket_mb: 0.0005",
                        mesh=MeshPlan.data_parallel())
        assert len(b._reduction.buckets) >= 2
        a.step(6, lambda it: data[it])
        b.step(6, lambda it: data[it])
        assert_bitwise(a, b)

    def test_adam_trajectory(self, rng):
        data = mlp_data(rng)
        cfg = 'type: "Adam" momentum: 0.9 momentum2: 0.999'
        a = make_solver(cfg, mesh=MeshPlan.data_parallel())
        b = make_solver(cfg + " reduce_overlap: true reduce_buckets: 2",
                        mesh=MeshPlan.data_parallel())
        a.step(6, lambda it: data[it])
        b.step(6, lambda it: data[it])
        assert_bitwise(a, b)


# ---------------------------------------------------------------------------
# Measurement surface (what the MULTICHIP dryrun reports)
# ---------------------------------------------------------------------------

class TestMeasurement:
    def test_bucketed_step_reduces_at_least_bucket_count_buffers(
            self, rng):
        # jax 0.9.0's CPU pipeline runs XLA's all-reduce combiner: the
        # three bucket psums (and the loss psum) come out as ONE
        # tuple-typed all-reduce with one operand per bucket, so the
        # bucket count is held on operands, not on ops
        data = mlp_data(rng, 1)
        b = make_solver("reduce_overlap: true reduce_buckets: 3",
                        mesh=MeshPlan.data_parallel())
        stats = reduction.collective_stats(b.step_hlo_text(data[0]))
        assert stats["all_reduces"] >= 1, stats
        assert stats["reduced_buffers"] >= 3, stats

    def test_collective_stats_counts_hlo_text(self):
        text = "\n".join([
            "%x = f32[8]{0} parameter(0)",
            "%ar = f32[8]{0} all-reduce(%x), replica_groups={}",
            "%y = f32[8]{0} add(%ar, %ar)",
            "%ar2 = f32[8]{0} all-reduce-start(%y)",
            "%g = f32[8]{0} get-tuple-element(%all-reduce), index=0",
            "%ar3 = (f32[8]{0}, f32[]) all-reduce(%y, %z), channel_id=1",
        ])
        stats = reduction.collective_stats(text)
        assert stats["all_reduces"] == 3
        assert stats["reduced_buffers"] == 4
        assert stats["overlap_span"] > 0

    def test_reduction_stats_shapes(self, rng):
        b = make_solver("reduce_overlap: true reduce_buckets: 3",
                        mesh=MeshPlan.data_parallel())
        stats = b.reduction_stats()
        assert stats["collectives_per_step"] == len(stats["bucket_bytes"])
        assert sum(stats["bucket_bytes"]) == sum(
            int(np.prod(np.shape(a)) * 4)
            for lp in b.params.values() for a in lp.values())
        assert make_solver().reduction_stats() is None

    def test_tpu_overlap_flags_env_application(self):
        env = {}
        assert reduction.apply_tpu_overlap_flags(env)
        assert "latency_hiding_scheduler" in env["LIBTPU_INIT_ARGS"]
        assert not reduction.apply_tpu_overlap_flags(env)  # idempotent
        env2 = {"CAFFE_TPU_NO_OVERLAP_FLAGS": "1"}
        assert not reduction.apply_tpu_overlap_flags(env2)
        assert "LIBTPU_INIT_ARGS" not in env2

    def test_tpu_overlap_flags_respect_explicit_operator_value(self):
        # an operator's explicit `=false` opt-out must not be
        # contradicted with a second `=true` copy of the same flag
        env = {"LIBTPU_INIT_ARGS":
               "--xla_tpu_enable_latency_hiding_scheduler=false"}
        reduction.apply_tpu_overlap_flags(env)
        args = env["LIBTPU_INIT_ARGS"]
        assert args.count("latency_hiding_scheduler") == 1
        assert "latency_hiding_scheduler=true" not in args
        # flags the operator did NOT spell are still appended
        assert "async_collective_fusion=true" in args


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------

class TestCLIPlumbing:
    def test_cli_byte_budget_overrides_prototxt_bucket_count(
            self, tmp_path, caplog, monkeypatch):
        """A recipe pinning `reduce_buckets` must be switchable to
        byte-budget sizing from the CLI without editing the prototxt —
        the CLI sizing mode clears the prototxt's OTHER mode instead of
        tripping the solver's "not both" validation."""
        from caffe_mpi_tpu.tools.cli import main
        monkeypatch.setenv("CAFFE_TPU_NO_OVERLAP_FLAGS", "1")
        net = tmp_path / "net.prototxt"
        net.write_text(MLP_NET)
        sf = tmp_path / "solver.prototxt"
        sf.write_text(
            f'net: "{net}"\nbase_lr: 0.05 momentum: 0.9\n'
            f'lr_policy: "fixed" max_iter: 2 random_seed: 5\n'
            f'snapshot_prefix: "{tmp_path}/snap"\n'
            f'reduce_overlap: true\nreduce_buckets: 4\n')
        with caplog.at_level(logging.INFO, "caffe_mpi_tpu.solver"):
            assert main(["train", "-solver", str(sf), "-synthetic",
                         "-gpu", "all", "-grad_bucket_mb", "0.001"]) == 0
        # byte-budget mode engaged: > 4 buckets proves the 0.001 MiB
        # budget sized them, not the prototxt count it overrode
        msgs = [r.message for r in caplog.records
                if "overlapped bucketed reduction" in r.message]
        assert msgs, caplog.records
