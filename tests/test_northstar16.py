"""16-virtual-device north-star topology tests.

BASELINE.md's capability ladder ends at ResNet-50 on a v5e-16 slice; the
reference's closest test is the in-process multi-GPU solver run
(reference src/caffe/test/test_gradient_based_solver.cpp:201-217 — real
P2PManager over k devices, constant data so k doesn't change results).
This file proves the two 16-way layouts the ladder needs, on 16 virtual
CPU devices (the suite's own process is pinned to 8, so the 16-device
work runs in a worker subprocess):

- data=8 x model=2 (DP x TP): the mesh BASELINE.md names for the
  16-chip rung, with a tensor-parallel dense layer;
- data=16 + ZeRO-1: pure DP at width 16 with optimizer state sharded
  across all devices.

Both must land on the SAME final parameters as a single-device run on
identical global batches — 16-way GSPMD partitioning is value-neutral.
The full-feature dryrun (dp x tp + SP + PP + EP + prototxt surfaces) at
16 devices is covered by test_dryrun_16, which drives the driver's own
__graft_entry__.dryrun_multichip(16) self-spawning path.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.abspath(os.path.join(_HERE, os.pardir))

NET = """
name: "ns16_mlp"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 32 dim: 8 } shape { dim: 32 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
        inner_product_param { num_output: 32 weight_filler { type: "xavier" } } }
layer { name: "r" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "y"
        inner_product_param { num_output: 4 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t" top: "l" }
"""
SOLVER_TEXT = ('base_lr: 0.05 momentum: 0.9 lr_policy: "fixed" max_iter: 50 '
               'type: "SGD" random_seed: 7')
N_STEPS = 6
GLOBAL_BATCH = 32  # 2 per device at data=16


def global_batches(n, seed=3):
    r = np.random.RandomState(seed)
    return [{"x": r.randn(GLOBAL_BATCH, 8).astype(np.float32),
             "t": r.randint(0, 4, GLOBAL_BATCH)} for _ in range(n)]


def _run_worker(tmp_path, mode):
    out = tmp_path / f"{mode}.npz"
    # the worker sets its own 16-device CPU pin; drop the suite's
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(_HERE, "northstar16_worker.py"),
         mode, str(out)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600)
    assert p.returncode == 0, f"worker {mode} failed:\n{p.stdout[-3000:]}"
    return np.load(out)


def _single_device_reference():
    import jax.numpy as jnp
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver

    sp = SolverParameter.from_text(SOLVER_TEXT)
    sp.net_param = NetParameter.from_text(NET)
    solver = Solver(sp)
    data = global_batches(N_STEPS)
    solver.step(N_STEPS, lambda it: {
        "x": jnp.asarray(data[it]["x"]), "t": jnp.asarray(data[it]["t"])})
    return solver


@pytest.fixture(scope="module")
def reference_params():
    s = _single_device_reference()
    return {"ip1_w": np.asarray(s.params["ip1"]["weight"]),
            "ip2_w": np.asarray(s.params["ip2"]["weight"])}


@pytest.mark.slow
def test_dp8_tp2_matches_single_device(tmp_path, reference_params):
    got = _run_worker(tmp_path, "dp8_tp2")
    for k in ("ip1_w", "ip2_w"):
        np.testing.assert_allclose(got[k], reference_params[k],
                                   rtol=5e-4, atol=1e-6, err_msg=k)


@pytest.mark.slow
def test_dp16_zero1_matches_single_device(tmp_path, reference_params):
    got = _run_worker(tmp_path, "dp16_zero1")
    for k in ("ip1_w", "ip2_w"):
        np.testing.assert_allclose(got[k], reference_params[k],
                                   rtol=5e-4, atol=1e-6, err_msg=k)


@pytest.mark.slow
def test_weak_scaling_reduction_1_to_8():
    """ISSUE 6 weak-scaling sweep (parallel/reduction.py — reference
    ReduceAndUpdate, net.cpp:757-913): at each data-parallel width the
    bucketed-overlapped step must land on bitwise-identical params vs
    the implicit GSPMD reduction, and every multi-device width must
    reduce >= reduce_buckets separate buffers per compiled step (the
    CPU pipeline's all-reduce combiner may merge the ops, never the
    operands; whether the TPU scheduler overlaps them with the remaining
    backward is a trace question — ROADMAP Speed 8). n=1 is the fallback baseline: nothing to reduce."""
    sys.path.insert(0, _ROOT)
    import __graft_entry__
    rows = __graft_entry__.weak_scaling_reduction((1, 2, 4, 8))
    assert [r["n_data"] for r in rows] == [1, 2, 4, 8]
    assert all(r["bitwise_vs_implicit"] for r in rows), rows
    for r in rows:
        if r["n_data"] == 1:
            assert r["mode"] == "implicit"
            continue
        assert r["mode"] == "bucketed"
        assert r["hlo_reduced_buffers"] >= r["collectives_per_step"] >= 3, r
        assert sum(r["bucket_bytes"]) > 0


@pytest.mark.slow
def test_dryrun_16():
    """The driver's own dryrun at 16 devices: dp x tp train step + ZeRO-1,
    ring-attention SP, 16-stage PP, 16-expert EP, prototxt Pipeline + SP
    surfaces — the full MULTICHIP check at the north-star width.
    dryrun_multichip self-spawns a fresh 16-device interpreter when the
    suite's 8-device client can't serve it."""
    sys.path.insert(0, _ROOT)
    import __graft_entry__
    __graft_entry__.dryrun_multichip(16)
