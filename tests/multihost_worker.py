"""Subprocess target for the 2-process multi-host tests (DP and ZeRO-1).

Run as: python multihost_worker.py <coordinator> <num_procs> <proc_id> \
            <out.npz> [dp|zero]

mode "zero" (default "dp") trains with zero_stage: 1 — optimizer slots
sharded across BOTH processes — and takes a snapshot whose history
gather runs the collective process_allgather path.

Each process is one "host" of a jax.distributed cluster on localhost
(CPU backend, 2 local devices each -> 4 global). The process feeds only
its LOCAL slice of the global batch through MeshPlan.shard_feeds, which
on process_count() > 1 assembles the global array from process-local
shards (jax.make_array_from_process_local_data) — the multi-host branch
of parallel/mesh.py:shard_feeds that single-process tests cannot reach.
Process 0 writes the final params for the parent to compare against a
single-process run on the same global batches (test_multihost.py, which
also owns the shared net/batch fixtures).
"""

import os
import sys

# one process = one simulated 2-device host
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir))
sys.path.insert(0, _HERE)

import jax  # noqa: E402

# pin the CPU platform the way tests/conftest.py does — backends init
# lazily, so an explicit update before any computation takes effect
# whatever JAX_PLATFORMS the launching shell carries
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from caffe_mpi_tpu.parallel import MeshPlan  # noqa: E402
from caffe_mpi_tpu.parallel.mesh import init_distributed  # noqa: E402
from caffe_mpi_tpu.proto import NetParameter, SolverParameter  # noqa: E402
from caffe_mpi_tpu.solver import Solver  # noqa: E402
from test_multihost import (  # noqa: E402
    GLOBAL_BATCH, N_STEPS, NET, SOLVER_TEXT, global_batches)


def main():
    coordinator, num_procs, proc_id, out_path = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else "dp"
    init_distributed(coordinator, num_procs, proc_id)
    assert jax.process_count() == num_procs, jax.process_count()
    assert len(jax.devices()) == 2 * num_procs, len(jax.devices())

    plan = MeshPlan.data_parallel()
    text = SOLVER_TEXT + (" zero_stage: 1" if mode == "zero" else "")
    sp = SolverParameter.from_text(text)
    sp.net_param = NetParameter.from_text(NET)
    solver = Solver(sp, mesh=plan, rank=proc_id)
    if mode == "zero":
        # slots of dim-0-divisible params really live split over 'data'
        # spanning BOTH processes (the multi-host ZeRO case)
        (hist,) = solver.opt_state["ip1"]["weight"]
        assert hist.sharding.spec[0] == "data", hist.sharding.spec
        assert not hist.is_fully_addressable  # remote shards exist

    data = global_batches(N_STEPS)
    local = GLOBAL_BATCH // num_procs

    def feed(it):
        # this process's contiguous slice of the global batch (the
        # Feeder's rank striping, hand-done for the fixture)
        b = data[it]
        sl = slice(proc_id * local, (proc_id + 1) * local)
        return {"x": jnp.asarray(b["x"][sl]), "t": jnp.asarray(b["t"][sl])}

    solver.step(N_STEPS, feed)
    if mode == "zero":
        # snapshot with remote-sharded slots: the history gather is a
        # COLLECTIVE process_allgather, so every rank enters snapshot();
        # async falls back to blocking (collective order must stay
        # rank-identical); only rank 0 writes the two files
        solver.sp.snapshot_prefix = out_path + ".snap"
        solver.snapshot(block=False)
        solver.wait_snapshots()
    if proc_id == 0:
        # params are replicated, so process 0's local replica is the
        # global value
        np.savez(out_path,
                 ip1_w=np.asarray(solver.params["ip1"]["weight"]),
                 ip2_w=np.asarray(solver.params["ip2"]["weight"]))
    jax.distributed.shutdown()
    print(f"proc {proc_id}: OK")


if __name__ == "__main__":
    main()
