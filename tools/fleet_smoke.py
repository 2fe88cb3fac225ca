#!/usr/bin/env python
"""Serving-fleet replica-kill + rolling-swap smoke (ISSUE 18) —
prints ONE JSON line.

The fleet contract end to end on CPU, with REAL replica processes
(the multi-process recipe of tools/multihost_smoke.py applied to the
serving plane): a FleetSupervisor spawns 2 `caffe serve` replicas
behind the typed-retry router, the fault plane kills one at a
heartbeat boundary (`replica_dead` site) while traffic flows, and the
smoke asserts the whole survivable story:

  1. every routed request resolves TYPED (200 or a machine-readable
     kind) — zero unresolved, zero untyped failures across the kill;
  2. the survivor absorbs the retried sheds: kill-phase p99 holds
     within 1.5x the 2-replica baseline (+25 ms CI-noise floor);
  3. the supervisor journals `replica_dead`, respawns the victim, and
     re-admits it only after its readyz gate;
  4. the respawned replica starts BANK-WARM: `compile_count ==
     bank_misses == 0`, every bucket a bank hit (PR 17's cold-start
     claim at fleet granularity);
  5. a rolling swap lands on every replica with zero recompiles and
     visibly changed scores; a candidate the canary rejects (NaN
     weights) raises a typed SwapError with every replica still
     serving the previous scores BITWISE (the staged-copy-rot site
     `fleet_swap_canary_bad` and the mid-rollout rollback are held at
     unit level in tests/test_serving_fleet.py).

Usage: python tools/fleet_smoke.py [--json] [--workdir D]
Exit 0 iff every claim held.
CPU-forced by design: it has not run on a chip.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

DEPLOY = """
name: "fleet_toy"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 8 dim: 3 dim: 12 dim: 12 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 4 kernel_size: 3 stride: 2
          weight_filler { type: "xavier" } } }
layer { name: "ip" type: "InnerProduct" bottom: "c" top: "score"
        inner_product_param { num_output: 6
          weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "score" top: "prob" }
"""

N_REPLICAS = 2
VICTIM = 1          # spawned second -> bank-warm, fast admission
REPLICA_DEADLINE = 2.0
# the victim's ReplicaBeat interval is deadline/4 = 0.5 s; beat 40 puts
# the death ~20 s after its beats arm — far past admission + baseline,
# squarely inside the kill-phase traffic loop below
KILL_AT_BEAT = 40
BASELINE_N = 40
P99_FLOOR_MS = 25.0  # absorbs CI scheduling noise on sub-50ms p99s


def _probe_png():
    import numpy as np
    from PIL import Image
    rng = np.random.RandomState(7)
    buf = io.BytesIO()
    Image.fromarray(rng.randint(0, 255, (12, 12, 3), np.uint8)
                    ).save(buf, format="PNG")
    return buf.getvalue()


def _send(router, png):
    t0 = time.perf_counter()
    status, doc = router.classify(png, "image/png")
    return status, doc, (time.perf_counter() - t0) * 1e3


def _p99(ms):
    if not ms:
        return float("nan")
    return sorted(ms)[max(0, int(len(ms) * 0.99) - 1)]


def _replica_scores(router, png):
    """Each replica's verbatim classify response for one probe — the
    bitwise-rollback comparisons key on exact doc equality."""
    out = {}
    for h in list(router._handles):
        status, doc = h.client.classify(png, "image/png")
        out[h.rid] = (status, json.dumps(doc, sort_keys=True))
    return out


def run_fleet_smoke(workdir: str = "") -> dict:
    # CPU before any jax computation: a chip belongs to one process
    # at a time, and this smoke runs 2 replica processes + a parent
    # (CPU-forced by design; the fleet has not run on a chip)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import caffe_mpi_tpu.pycaffe as caffe
    from caffe_mpi_tpu.proto.config import ServingParameter
    from caffe_mpi_tpu.serving.errors import SwapError
    from caffe_mpi_tpu.serving.fleet import FleetSupervisor
    from caffe_mpi_tpu.utils import resilience

    root = workdir or tempfile.mkdtemp(prefix="caffe_fleet_smoke_")
    os.makedirs(root, exist_ok=True)
    report: dict = {"workdir": root, "replicas": N_REPLICAS}
    model = os.path.join(root, "deploy.prototxt")
    with open(model, "w") as f:
        f.write(DEPLOY)
    net = caffe.Net(model, caffe.TEST)
    w1 = os.path.join(root, "w1.caffemodel")
    net.save(w1)
    fleet_dir = os.path.join(root, "fleet")
    fdir = os.path.join(root, "faults")
    os.makedirs(fdir, exist_ok=True)

    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "CAFFE_TPU_FAULTS",
                             "CAFFE_TPU_FAULTS_DIR",
                             "CAFFE_SUPERVISED_CHILD")}
    base_env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    # the .done marker in CAFFE_TPU_FAULTS_DIR keeps the respawned
    # victim — which inherits this same env — from re-dying
    victim_env = {VICTIM: {
        "CAFFE_TPU_FAULTS": f"replica_dead:1:0:{KILL_AT_BEAT}",
        "CAFFE_TPU_FAULTS_DIR": fdir}}
    sp = ServingParameter()
    sp.serve_window_ms = 2.0
    sup = FleetSupervisor(model, w1, N_REPLICAS, fleet_dir,
                          serving_param=sp, base_env=base_env,
                          replica_env=victim_env,
                          replica_deadline=REPLICA_DEADLINE)
    png = _probe_png()
    ok = True
    t_start = time.perf_counter()
    try:
        sup.start()
        router = sup.router
        report["spawn_s"] = round(time.perf_counter() - t_start, 1)

        # -- baseline: both replicas up -------------------------------
        lat = []
        for _ in range(BASELINE_N):
            status, doc, ms = _send(router, png)
            if status != 200:
                ok = False
                report.setdefault("baseline_failures", []).append(doc)
            lat.append(ms)
        base_p99 = _p99(lat)
        report["baseline"] = {"n": BASELINE_N,
                              "p99_ms": round(base_p99, 2)}

        # -- kill phase: traffic until the heartbeat mourns -----------
        kill_lat, untyped, n_kill = [], 0, 0
        deadline = time.time() + 90
        death_at = None
        while time.time() < deadline:
            status, doc, ms = _send(router, png)
            n_kill += 1
            if status == 200:
                kill_lat.append(ms)
            elif not doc.get("kind"):
                untyped += 1
            if router.health()["replica_deaths"] >= 1:
                death_at = time.perf_counter()
                break
        # keep the survivor under load through detection + respawn
        readmit_deadline = time.time() + 120
        while time.time() < readmit_deadline:
            status, doc, ms = _send(router, png)
            n_kill += 1
            if status == 200:
                kill_lat.append(ms)
            elif not doc.get("kind"):
                untyped += 1
            h = router.health()
            if h["respawns"] >= 1 and router.ready()[0]:
                break
            time.sleep(0.05)
        kill_p99 = _p99(kill_lat)
        p99_bound = max(1.5 * base_p99, base_p99 + P99_FLOOR_MS)
        report["kill"] = {
            "requests": n_kill,
            "untyped_failures": untyped,
            "death_detected": death_at is not None,
            "p99_ms": round(kill_p99, 2),
            "p99_bound_ms": round(p99_bound, 2),
            "p99_holds": bool(kill_p99 <= p99_bound),
            "readmitted": bool(router.ready()[0]),
            "retries": router.retries,
            "conn_errors": router.conn_errors,
        }
        ok = ok and untyped == 0 and death_at is not None \
            and report["kill"]["p99_holds"] and report["kill"]["readmitted"]

        # -- respawned replica must be bank-warm: ZERO compiles -------
        vdoc = router.stats()["replicas"][str(VICTIM)]
        bank = vdoc.get("bank", {})
        report["respawn"] = {
            "compile_count": vdoc.get("compile_count"),
            "bank_misses": bank.get("misses"),
            "bank_hits": bank.get("hits"),
            "warmed_buckets": vdoc.get("warmed_buckets"),
        }
        bank_warm = (vdoc.get("compile_count") == 0
                     and bank.get("misses") == 0
                     and bank.get("hits") == vdoc.get("warmed_buckets"))
        report["respawn"]["bank_warm_zero_compile"] = bool(bank_warm)
        ok = ok and bank_warm

        # -- journal: the death + respawn are durable evidence --------
        jdoc = resilience.read_run_manifest(
            os.path.join(fleet_dir, "fleet") + ".serve") or {}
        report["journal"] = {"reason": jdoc.get("reason"),
                             "replica_deaths": jdoc.get("replica_deaths"),
                             "respawns": jdoc.get("respawns")}
        ok = ok and (jdoc.get("replica_deaths") or 0) >= 1 \
            and (jdoc.get("respawns") or 0) >= 1

        # -- rolling swap: lands everywhere, zero recompiles ----------
        pre_swap = _replica_scores(router, png)
        compiles_before = {rid: doc.get("compile_count")
                           for rid, doc in router.stats()["replicas"].items()}
        net.params["ip"][0].data = net.params["ip"][0].data * 3.0
        w2 = os.path.join(root, "w2.caffemodel")
        net.save(w2)
        router.swap_weights("default", w2, source="smoke_v2")
        post_swap = _replica_scores(router, png)
        rdocs = router.stats()["replicas"]
        report["swap"] = {
            "swaps_per_replica": [doc.get("swaps") for doc in
                                  rdocs.values()],
            "scores_changed_everywhere": all(
                pre_swap[rid][1] != post_swap[rid][1]
                and post_swap[rid][0] == 200 for rid in pre_swap),
            "zero_recompile": all(
                doc.get("compile_count") == compiles_before[rid]
                for rid, doc in rdocs.items()),
        }
        ok = ok and all(s == 1 for s in report["swap"]["swaps_per_replica"]) \
            and report["swap"]["scores_changed_everywhere"] \
            and report["swap"]["zero_recompile"]

        # -- rejected candidate: fleet keeps serving BITWISE ----------
        net.params["ip"][0].data = np.full_like(
            net.params["ip"][0].data, np.nan)
        w_bad = os.path.join(root, "w_bad.caffemodel")
        net.save(w_bad)
        typed_reject = False
        try:
            router.swap_weights("default", w_bad, source="smoke_bad")
        except SwapError:
            typed_reject = True
        after_reject = _replica_scores(router, png)
        report["reject"] = {
            "swap_error_typed": typed_reject,
            "scores_bitwise_kept_everywhere": all(
                post_swap[rid] == after_reject[rid] for rid in post_swap),
            "rejections": router.swap_rejections,
        }
        ok = ok and typed_reject \
            and report["reject"]["scores_bitwise_kept_everywhere"] \
            and router.swap_rejections >= 1
    finally:
        sup.stop()
    report["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    report["ok"] = bool(ok)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--workdir", default="")
    args = ap.parse_args()
    keep = bool(args.workdir)
    report = run_fleet_smoke(args.workdir)
    print(json.dumps({"fleet_smoke": report}) if args.json
          else json.dumps(report, indent=1))
    if not keep and report.get("ok"):
        shutil.rmtree(report["workdir"], ignore_errors=True)
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
