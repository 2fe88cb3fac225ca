"""Subprocess target for the 16-virtual-device north-star tests.

Run as: python northstar16_worker.py <mode> <out.npz>
  mode "dp8_tp2"    — data=8 x model=2 mesh, ip1 tensor-parallel
  mode "dp16_zero1" — data=16 mesh with ZeRO-1 optimizer sharding

BASELINE.md's ladder ends at a v5e-16 slice (ResNet-50, 16 chips); the
reference's in-process analogue is its k-device multi-GPU solver test
(reference src/caffe/test/test_gradient_based_solver.cpp:201-217). No
16-chip hardware exists here, so the topology runs on 16 virtual CPU
devices — the same GSPMD partitioning XLA would emit for the real slice.
The parent (test_northstar16.py) compares the final params against a
single-device run on identical global batches: the 16-way shardings must
be value-neutral.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))
sys.path.insert(0, _HERE)

import jax  # noqa: E402

# pin the CPU platform before any computation (backends init lazily)
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from caffe_mpi_tpu.parallel import MeshPlan  # noqa: E402
from caffe_mpi_tpu.proto import NetParameter, SolverParameter  # noqa: E402
from caffe_mpi_tpu.solver import Solver  # noqa: E402
from test_northstar16 import (  # noqa: E402
    N_STEPS, NET, SOLVER_TEXT, global_batches)


def main():
    mode, out_path = sys.argv[1], sys.argv[2]
    assert len(jax.devices()) == 16, len(jax.devices())

    if mode == "dp8_tp2":
        plan = MeshPlan.from_shape(data=8, model=2)
        sp = SolverParameter.from_text(SOLVER_TEXT)
        shardings = {"ip1": ("model", None)}
    elif mode == "dp16_zero1":
        plan = MeshPlan.from_shape(data=16, model=1)
        sp = SolverParameter.from_text(SOLVER_TEXT + " zero_stage: 1")
        shardings = None
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    sp.net_param = NetParameter.from_text(NET)
    solver = Solver(sp, mesh=plan, param_shardings=shardings)

    if mode == "dp16_zero1":
        # ZeRO-1: the momentum slot really is split 16 ways over 'data'
        (hist,) = solver.opt_state["ip1"]["weight"]
        assert hist.sharding.spec and hist.sharding.spec[0] == "data", \
            hist.sharding.spec
        assert len(hist.sharding.device_set) == 16
    else:
        # TP: ip1's weight is materially sharded over 'model'
        w = solver.params["ip1"]["weight"]
        assert not w.sharding.is_fully_replicated, w.sharding

    data = global_batches(N_STEPS)
    solver.step(N_STEPS, lambda it: {
        "x": jnp.asarray(data[it]["x"]), "t": jnp.asarray(data[it]["t"])})

    np.savez(out_path,
             ip1_w=np.asarray(solver.params["ip1"]["weight"]),
             ip2_w=np.asarray(solver.params["ip2"]["weight"]))
    print(f"northstar16 {mode}: OK")


if __name__ == "__main__":
    main()
