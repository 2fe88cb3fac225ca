#!/usr/bin/env python
"""2-process elastic-cluster recovery smoke (ISSUE 11).

The reference's multi-node path (mpirun + Clusters + global NCCL
communicator, clusters.cpp:8-45, parallel.cpp:166-169) dies with any
rank; this smoke proves the elastic replacement survives one. Two
`caffe train -hosts 2` workers (each its own `--max-restarts`
supervisor) form a real jax.distributed cluster on localhost; the
fault plane kills worker 1 at a heartbeat boundary (`host_loss`
site); worker 0's heartbeat must journal `host_lost:1` and exit 87
within `host_deadline`; both supervisors then perform the coordinated
`--resume auto` restart, the cluster re-forms, and the recovered
run's final weights must be BIT-IDENTICAL to an uninterrupted
2-process baseline — the same discipline as
tests/test_fault_tolerance.py, at host granularity.

Workers are CPU-forced: this jaxlib's CPU backend cannot form
multiprocess computations, so each host trains its local replica
(identical synthetic feeds + seeds keep the trajectories equal, which
is exactly the replicated-params invariant the global-mesh TPU path
maintains through collectives); what the smoke exercises is the
ELASTIC runtime — cluster formation, heartbeat loss detection,
journaled 87s, rank-0 resume publication, the exit barrier.

`--degrade` (ISSUE 19) runs the degraded-mode variant instead: the
same pair launches with `-min_hosts 1`, and host 1 dies PERMANENTLY
(its supervisor goes dark too, `host_perma_loss` fault site). Host
0's supervisor must run the generation protocol — publish generation
2 (`cluster_degraded`, world 1) and continue alone; when host 1's
supervisor revives it must park in rejoin-wait; rank 0 re-admits it
at a snapshot boundary (journaled `cluster_rejoin` exit 87), the
supervisors publish generation 3 (`cluster_regrown`, world 2), and
the regrown run's final weights must still be BIT-IDENTICAL to the
uninterrupted baseline.

Usage: python tools/multihost_smoke.py [--json] [--workdir D] [--degrade]
Exit 0 iff every assertion holds. Run by tests/test_multihost.py
(default mode) and tests/test_degraded.py (`--degrade`). CPU-forced by
design: `-hosts N` on one machine has not run on a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

NET = """
name: "mh_mlp"
layer { name: "in" type: "Input" top: "x" top: "t"
        input_param { shape { dim: 32 dim: 16 } shape { dim: 32 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "h"
        inner_product_param { num_output: 64
          weight_filler { type: "xavier" } } }
layer { name: "r" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "y"
        inner_product_param { num_output: 10
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "y" bottom: "t"
        top: "l" }
"""

# Sized by the CPU's iteration rate, ~3,300 a second since the plain
# step is the one program an iteration launches (750 before): the run
# has to outlast the kill beat plus the deadline, ~3 s in.
MAX_ITER = 16000
SNAP_EVERY = 2000  # first snapshot ~0.6 s in: well before the kill beat
# The deadline MUST undercut the killed worker's restart latency
# (supervisor backoff 1 s + interpreter/jax start ~1.3 s): the survivor
# has to detect the silence and exit 87 BEFORE the dead host's
# replacement reconnects, or the coordination service's incarnation
# check SIGABRTs the survivor first — recovery still converges (any
# nonzero exit restarts), but without the journaled host_lost exit
# this smoke asserts (docs/robustness.md "Multi-host elasticity").
HOST_DEADLINE = 1.0
KILL_AT_BEAT = 8  # ~2 s after worker 1's heartbeat arms (beat = 0.25 s)
# --degrade: how long host 1's SUPERVISOR stays dark after its worker
# dies (host_perma_loss arg). Must outlast host 0's loss detection
# (~host_deadline) + membership round (~2 s) so generation 2 exists
# before the revival — a too-early revival still converges (init
# timeout then rejoin-wait) but slower.
PERMA_DARK_S = 5.0
# --degrade trains longer: the degraded generation must still be
# mid-run (with snapshot boundaries ahead) when host 1 revives, or
# there is no grow-back to observe.
DEGRADE_MAX_ITER = 32000


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def build_workspace(root: str, max_iter: int = MAX_ITER) -> str:
    os.makedirs(root, exist_ok=True)
    net = os.path.join(root, "net.prototxt")
    with open(net, "w") as f:
        f.write(NET)
    solver = os.path.join(root, "solver.prototxt")
    with open(solver, "w") as f:
        f.write(f'net: "{net}"\nbase_lr: 0.05 momentum: 0.9\n'
                f'lr_policy: "fixed" max_iter: {max_iter} random_seed: 5\n'
                f'display: 0 snapshot: {SNAP_EVERY}\n')
    return solver


def run_pair(solver: str, prefix: str, port: int, *, kill_rank=None,
             faults_dir: str = "", timeout: float = 300.0,
             min_hosts: int = 0, perma_dark: float = 0.0):
    """Launch the 2 supervised workers, wait for both, return
    (returncodes, outputs). `min_hosts` > 0 arms the degraded-mode
    elastic supervisor; `perma_dark` > 0 additionally takes the killed
    rank's SUPERVISOR dark for that many seconds (host_perma_loss)."""
    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "CAFFE_TPU_FAULTS",
                             "CAFFE_TPU_FAULTS_DIR",
                             "CAFFE_SUPERVISED_CHILD")}
    base_env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
                    CAFFE_TPU_INIT_TIMEOUT="20")
    procs = []
    for i in range(2):
        env = dict(base_env)
        if kill_rank is not None and i == kill_rank:
            spec = f"host_loss:1:0:{KILL_AT_BEAT}"
            if perma_dark > 0:
                spec += f",host_perma_loss:1:0:{perma_dark}"
            env["CAFFE_TPU_FAULTS"] = spec
            env["CAFFE_TPU_FAULTS_DIR"] = faults_dir
        cmd = [sys.executable, "-m", "caffe_mpi_tpu.tools.cli", "train",
               "-solver", solver, "-synthetic",
               "-snapshot_prefix", prefix,
               "-hosts", "2", "-coordinator", f"localhost:{port}",
               "-host_id", str(i), "-host_deadline", str(HOST_DEADLINE),
               "-max_restarts", "3"]
        if min_hosts:
            cmd += ["-min_hosts", str(min_hosts)]
        procs.append(subprocess.Popen(
            cmd, env=env, cwd=_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs, rcs = [], []
    deadline = time.time() + timeout
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(deadline - time.time(), 5))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out = "TIMEOUT"
        outs.append(out)
        rcs.append(p.returncode)
    return rcs, outs


def final_weights(prefix: str, max_iter: int = MAX_ITER):
    from caffe_mpi_tpu.io import load_caffemodel
    path = f"{prefix}_iter_{max_iter}.caffemodel"
    if not os.path.exists(path):
        return None
    return load_caffemodel(path)


def weights_equal(a, b) -> bool:
    import numpy as np
    if a is None or b is None or set(a) != set(b):
        return False
    return all(np.array_equal(x, y)
               for ln in a for x, y in zip(a[ln], b[ln]))


def read_gen(prefix: str, g: int) -> dict:
    """One generation-history record from the run's cluster dir
    (resilience.write_generation's audit trail); {} when absent."""
    path = os.path.join(prefix + ".cluster", f"gen_{g}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def run_degrade(root: str, report: dict) -> bool:
    """Degraded-mode scenario (ISSUE 19): permanent host-1 loss ->
    generation 2 continues at world 1 -> revival parks in rejoin-wait
    -> snapshot-boundary grow-back to generation 3 at world 2 -> final
    weights bitwise-equal an uninterrupted baseline."""
    import re
    solver = build_workspace(root, max_iter=DEGRADE_MAX_ITER)
    ok = True

    t0 = time.time()
    base_prefix = os.path.join(root, "baseline", "s")
    rcs, outs = run_pair(solver, base_prefix, free_port(), min_hosts=1)
    report["baseline_rcs"] = rcs
    report["baseline_s"] = round(time.time() - t0, 1)
    if rcs != [0, 0]:
        ok = False
        report["baseline_tail"] = [o[-1500:] for o in outs]
    base_w = final_weights(base_prefix, DEGRADE_MAX_ITER)
    # a clean min_hosts run must stay implicit generation 1: no
    # failure ever happened, so no record may exist
    report["baseline_no_generations"] = not read_gen(base_prefix, 2)

    t0 = time.time()
    deg_prefix = os.path.join(root, "degrade", "s")
    fdir = os.path.join(root, "degrade_faults")
    os.makedirs(fdir, exist_ok=True)
    rcs, outs = run_pair(solver, deg_prefix, free_port(), kill_rank=1,
                         faults_dir=fdir, min_hosts=1,
                         perma_dark=PERMA_DARK_S, timeout=420.0)
    report["degrade_rcs"] = rcs
    report["degrade_s"] = round(time.time() - t0, 1)
    surv, killed = outs[0], outs[1]
    report["host_loss_detected"] = "heartbeat: host 1 silent" in surv
    g2, g3 = read_gen(deg_prefix, 2), read_gen(deg_prefix, 3)
    report["degraded_generation"] = (
        g2.get("reason") == "cluster_degraded"
        and g2.get("hosts") == [0] and g2.get("world") == 1)
    report["regrown_generation"] = (
        g3.get("reason") == "cluster_regrown"
        and g3.get("hosts") == [0, 1] and g3.get("world") == 2)
    report["parked_in_rejoin_wait"] = "rejoin-wait" in killed
    # rank 0 may only re-admit the revived host at a snapshot boundary
    # (solver._maybe_admit_rejoin journals the exact iteration)
    m = re.search(r"snapshot boundary iteration (\d+)", surv)
    report["rejoin_iter"] = int(m.group(1)) if m else None
    report["rejoin_at_snapshot_boundary"] = bool(
        m and int(m.group(1)) % SNAP_EVERY == 0)
    deg_w = final_weights(deg_prefix, DEGRADE_MAX_ITER)
    report["weights_bitwise_equal"] = weights_equal(base_w, deg_w)
    if rcs != [0, 0] or not (
            report["baseline_no_generations"]
            and report["host_loss_detected"]
            and report["degraded_generation"]
            and report["regrown_generation"]
            and report["parked_in_rejoin_wait"]
            and report["rejoin_at_snapshot_boundary"]
            and report["weights_bitwise_equal"]):
        ok = False
        report["degrade_tail"] = [o[-3000:] for o in outs]
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--workdir", default="")
    ap.add_argument("--degrade", action="store_true",
                    help="run the ISSUE 19 degraded-mode scenario "
                         "(permanent loss -> gen 2 at world 1 -> "
                         "rejoin -> gen 3) instead of the default "
                         "restart-all recovery")
    args = ap.parse_args()
    root = args.workdir or tempfile.mkdtemp(prefix="caffe_mh_smoke_")
    keep = bool(args.workdir)
    if args.degrade:
        report = {"workdir": root, "mode": "degrade"}
        try:
            ok = run_degrade(root, report)
            report["ok"] = ok
            print(json.dumps({"multihost_smoke": report}) if args.json
                  else json.dumps(report, indent=1))
            return 0 if ok else 1
        finally:
            if not keep:
                shutil.rmtree(root, ignore_errors=True)
    solver = build_workspace(root)
    report: dict = {"workdir": root}
    ok = True
    try:
        t0 = time.time()
        base_prefix = os.path.join(root, "baseline", "s")
        rcs, outs = run_pair(solver, base_prefix, free_port())
        report["baseline_rcs"] = rcs
        report["baseline_s"] = round(time.time() - t0, 1)
        if rcs != [0, 0]:
            ok = False
            report["baseline_tail"] = [o[-1500:] for o in outs]
        base_w = final_weights(base_prefix)

        t0 = time.time()
        rec_prefix = os.path.join(root, "recovery", "s")
        fdir = os.path.join(root, "recovery_faults")
        os.makedirs(fdir, exist_ok=True)
        rcs, outs = run_pair(solver, rec_prefix, free_port(),
                             kill_rank=1, faults_dir=fdir)
        report["recovery_rcs"] = rcs
        report["recovery_s"] = round(time.time() - t0, 1)
        surv, killed = outs[0], outs[1]
        report["host_loss_detected"] = "heartbeat: host 1 silent" in surv
        report["coordinated_restart"] = (
            "child failed (fault/cluster)" in surv
            and "child failed (fault/cluster)" in killed)
        report["resumed_from_snapshot"] = "Restored solver state" in (
            surv + killed)
        rec_w = final_weights(rec_prefix)
        report["weights_bitwise_equal"] = weights_equal(base_w, rec_w)
        # resumed_from_snapshot is part of the gate: a kill that lands
        # before the first snapshot would still replay bit-identically
        # from iteration 0, silently skipping the rank-0
        # resume-publication / --resume auto restore path this smoke
        # exists to prove
        if rcs != [0, 0] or not (report["host_loss_detected"]
                                 and report["coordinated_restart"]
                                 and report["resumed_from_snapshot"]
                                 and report["weights_bitwise_equal"]):
            ok = False
            report["recovery_tail"] = [o[-2500:] for o in outs]
        report["ok"] = ok
        print(json.dumps({"multihost_smoke": report}) if args.json
              else json.dumps(report, indent=1))
        return 0 if ok else 1
    finally:
        if not keep:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
