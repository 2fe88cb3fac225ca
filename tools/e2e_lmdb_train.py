#!/usr/bin/env python
"""End-to-end AlexNet training from a real LMDB through the full host
pipeline (Feeder -> transform/staging -> device), NOT synthetic
on-device data.

VERDICT r4 weak #3: every committed TPU training number used synthetic
on-device feeds, so the claim 'the host pipeline can feed the flagship'
had no measured evidence. This script is the measurement: it builds a
synthetic-image LMDB once (reference analogue: examples/imagenet
create_imagenet.sh), points the real AlexNet topology's Data layers at
it (crop 227 + mirror + mean subtraction — the reference training
transform, data_transformer.cpp), trains N iterations with the same CLI
path `caffe train` uses, and prints e2e img/s to compare against the
synthetic-feed cell on the same chip (`python3 benchmarks/run.py
--workload alexnet_f32`; PERF.md). The gap between the two is the
host-pipeline cost on that host. No benchmark cell drives the Feeder
yet (ROADMAP Reach 2), so neither side of that comparison is in the
ledger.

Usage: python tools/e2e_lmdb_train.py [--batch N] [--iters N] [--records N]
Runs on whatever platform jax selects (pin the CPU with JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def build_db(workdir: str, n: int, shape=(3, 256, 256),
             codec: str = "jpeg") -> tuple[str, str]:
    """Synthetic separable-cluster LMDB + mean file (cached across runs:
    rebuilding 1k 256x256 records costs ~10s of host time). Records are
    JPEG-encoded Datums by default (ISSUE 10) — the ImageNet-convert
    layout, where the host pipeline pays a real decode per record;
    `codec='none'` writes raw datums (the pre-ISSUE-10 layout). The mean
    file is computed over the PRE-encode pixels (the ~1 LSB JPEG
    round-trip shift is noise at training scale)."""
    import numpy as np
    from examples.common import synthetic_clusters
    from caffe_mpi_tpu.data.datasets import encode_datum, encode_datum_image
    from caffe_mpi_tpu.data.lmdb_io import write_lmdb
    from caffe_mpi_tpu.io import save_blob_binaryproto

    tag = "" if codec == "none" else f"_{codec}"
    db = os.path.join(workdir, f"e2e_train_lmdb_{n}{tag}")
    mean = os.path.join(workdir, f"e2e_mean_{n}.binaryproto")
    if os.path.isdir(db) and os.path.exists(mean):
        return db, mean

    # chunked generation (same reason as examples/imagenet/
    # create_imagenet.py): one 1024-record draw at 3x256x256 peaks at
    # multiple GB of transient int arrays on this host
    mean_acc = np.zeros(shape, np.float64)

    def records():
        chunk = 64
        for lo in range(0, n, chunk):
            k = min(chunk, n - lo)
            imgs, labels = synthetic_clusters(k, shape, seed=7 + lo,
                                              classes=10)
            mean_acc[...] += imgs.sum(axis=0, dtype=np.float64)
            for i in range(k):
                key = f"{lo + i:08d}".encode()
                if codec == "none":
                    yield key, encode_datum(imgs[i], int(labels[i]))
                else:
                    yield key, encode_datum_image(imgs[i], int(labels[i]),
                                                  codec)

    write_lmdb(db, records())
    save_blob_binaryproto(mean, (mean_acc / n).astype(np.float32)[None])
    return db, mean


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--records", type=int, default=1024)
    p.add_argument("--workdir", default="/tmp/caffe_e2e_lmdb")
    p.add_argument("--test-iters", type=int, default=8,
                   help="test batches per fused-eval telemetry pass")
    p.add_argument("--test-chunk", type=int, default=4,
                   help="test batches fused per eval dispatch (solver "
                   "test_chunk)")
    # survivable-training knobs (ISSUE 3, utils/resilience.py)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="supervised mode: run this script in a contained "
                   "child and restart it (--resume auto, exponential "
                   "backoff) up to N times on failure — watchdog "
                   "hard-exits included. 0 = unsupervised")
    p.add_argument("--watchdog-deadline", type=float, default=0.0,
                   help="dispatch watchdog deadline in seconds (journal "
                   "+ hard-exit 86 on a stuck device sync); 0 = off")
    p.add_argument("--snapshot-every", type=int, default=0,
                   help="write a verified atomic snapshot every N "
                   "iterations (0 = only useful under --max-restarts, "
                   "where it defaults to 10)")
    p.add_argument("--snapshot-keep", type=int, default=3,
                   help="keep only the newest N snapshots (solver "
                   "snapshot_keep GC; never deletes the newest "
                   "verified one)")
    p.add_argument("--resume", default="",
                   help="'auto' = resume from the newest verified "
                   "snapshot in the workdir (set by the supervisor on "
                   "restart)")
    # self-healing knobs (ISSUE 4, docs/robustness.md)
    p.add_argument("--train-guard", type=int, default=1,
                   help="1 (default): run with the on-device non-finite "
                   "guard armed, reporting skipped_steps + guard_syncs "
                   "so the guard's ~zero overhead is measured on the "
                   "real pipeline; 0 = unguarded")
    # ingestion knobs (ISSUE 10)
    p.add_argument("--codec", default="jpeg",
                   choices=["jpeg", "png", "none"],
                   help="record encoding for the synthetic LMDB "
                   "(default jpeg — the host pipeline pays a real "
                   "decode per record; 'none' = raw datums, the "
                   "pre-ISSUE-10 layout)")
    p.add_argument("--decoded-cache-mb", type=float, default=0.0,
                   help="decoded-record cache budget (solver "
                   "decoded_cache_mb); epochs after the first skip "
                   "read+crc+decode for the cached span")
    p.add_argument("--require-native-decode", action="store_true",
                   help="exit nonzero unless the native decode plane "
                   "actually decoded records this run")
    args = p.parse_args()

    if args.max_restarts > 0 \
            and os.environ.get("CAFFE_SUPERVISED_CHILD") != "1":
        # supervisor half: contained child + exponential backoff +
        # crash-loop guard; restarts resume from the newest verified
        # snapshot (the same harness `cli train --max-restarts` uses)
        from caffe_mpi_tpu.utils import resilience
        argv, skip = [], False
        for tok in sys.argv[1:]:  # child argv = ours minus --max-restarts
            if skip:
                skip = False
                continue
            if tok == "--max-restarts":
                skip = True
                continue
            if tok.startswith("--max-restarts="):
                continue
            argv.append(tok)
        base = [sys.executable, os.path.abspath(__file__)] + argv
        resume = base + (["--resume", "auto"]
                         if "--resume" not in argv
                         and not any(a.startswith("--resume=")
                                     for a in argv) else [])
        env = dict(os.environ, CAFFE_SUPERVISED_CHILD="1")
        prefix = os.path.join(args.workdir, "e2e_snap", "s")
        # exit 88 from the guarded child routes through the default
        # rewind policy (the child converts NumericAnomalyError below)
        return resilience.supervise(
            base, resume, args.max_restarts,
            failure_log=prefix + ".failures.log", env=env,
            anomaly_action="rewind")

    os.makedirs(args.workdir, exist_ok=True)
    db, mean = build_db(args.workdir, args.records, codec=args.codec)

    import jax
    import numpy as np
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver
    from caffe_mpi_tpu.tools.cli import _build_feeders
    from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache
    from caffe_mpi_tpu.utils.flops import peak_flops, train_flops_per_image

    enable_compile_cache()

    # the zoo AlexNet topology with its Input layer swapped for a Data
    # layer reading the LMDB (the reference's own train_val shape:
    # crop 227, mirror, mean file)
    npar = NetParameter.from_file(
        os.path.join(_ROOT, "models/alexnet/train_val.prototxt"))
    data_text = f"""
    name: "alexnet_lmdb"
    layer {{ name: "data" type: "Data" top: "data" top: "label"
            transform_param {{ crop_size: 227 mirror: true
                               mean_file: "{mean}" }}
            data_param {{ source: "{db}" batch_size: {args.batch}
                          backend: LMDB }} }}
    """
    head = NetParameter.from_text(data_text)
    npar.layer = list(head.layer) + [
        l for l in npar.layer if l.type != "Input"]
    sp = SolverParameter.from_text(
        'base_lr: 0.01 momentum: 0.9 lr_policy: "fixed" max_iter: 1000000 '
        'display: 0 random_seed: 3')
    sp.net_param = npar
    # fused-eval telemetry (ISSUE 2): a TEST-phase twin of the same net
    # reads the same LMDB; after the timed region two async eval passes
    # run overlapped with training to measure test_dispatches_per_pass
    # (= ceil(test_iter/test_chunk) + 1 param copy) and eval_stall_ms
    sp.test_iter = [args.test_iters]
    sp.test_chunk = max(args.test_chunk, 1)
    # survivable training (ISSUE 3): verified atomic snapshots with GC,
    # optional dispatch watchdog; the supervised restart lands on the
    # newest verified snapshot via --resume auto
    sp.snapshot_prefix = os.path.join(args.workdir, "e2e_snap", "s")
    snap_every = args.snapshot_every or (
        10 if os.environ.get("CAFFE_SUPERVISED_CHILD") == "1" else 0)
    if snap_every:
        sp.snapshot = snap_every
    sp.snapshot_keep = max(args.snapshot_keep, 0)
    sp.watchdog_deadline = max(args.watchdog_deadline, 0.0)
    # self-healing (ISSUE 4): non-finite guard in the train step; a
    # corrupt LMDB record would quarantine via the crc sidecar the
    # build_db writer published (journal next to the snapshots)
    sp.train_guard = bool(args.train_guard)
    # ingestion (ISSUE 10): optional decoded-record cache tier; the
    # Feeder engages the fused native decode path on its own when the
    # records are encoded and native/decode.cc is built
    if args.decoded_cache_mb:
        sp.decoded_cache_mb = args.decoded_cache_mb
    from caffe_mpi_tpu.utils import resilience
    resilience.QUARANTINE.configure(sp.snapshot_prefix
                                    + ".quarantine.json")

    solver = Solver(sp)
    if args.resume == "auto":
        solver.restore_auto()
    feeder = _build_feeders(solver.net, "TRAIN", solver_param=sp)
    assert feeder is not None, "Data layer did not produce a feeder"
    test_feeder = _build_feeders(solver.test_nets[0], "TEST",
                                 solver_param=sp)

    eval_line = ""
    try:
        # the first iteration compiles the step: the timed region
        # reuses it
        solver.step(3, feeder)
        jax.block_until_ready(solver.params)
        d0, g0 = solver.dispatch_count, solver.guard_sync_count
        t0 = time.perf_counter()
        solver.step(args.iters, feeder)
        jax.block_until_ready(solver.params)
        dt = time.perf_counter() - t0
        dispatches = solver.dispatch_count - d0
        guard_syncs = solver.guard_sync_count - g0

        # untimed fused-eval phase: boundaries fire during 6 more train
        # iters; the eval scan runs between train steps and the stall
        # counter records what the train loop actually lost
        solver.sp.test_interval = 3
        solver.test_all([test_feeder])  # compile eval programs off-clock
        td0, tp0, ts0 = (solver.test_dispatch_count, solver.test_pass_count,
                         solver.eval_stall_ms)
        solver.step(6, feeder, test_feed_fns=[test_feeder])
        jax.block_until_ready(solver.params)
        passes = solver.test_pass_count - tp0
        if passes:
            eval_line = (
                f", test_iter {args.test_iters} @ test_chunk "
                f"{solver.sp.test_chunk}: "
                f"{(solver.test_dispatch_count - td0) / passes:.1f} "
                f"test_dispatches_per_pass, "
                f"{(solver.eval_stall_ms - ts0) / passes:.1f} "
                f"eval_stall_ms")

        # ISSUE 10 both-sides measurement: host-pipeline SUPPLY rate
        # (per-worker batch-build throughput over the same LMDB,
        # prefetch queue bypassed so lookahead can't flatter it) vs the
        # train loop's CONSUMPTION rate (the e2e img/s above). Supply
        # must exceed consumption or the chips starve. Batches are pure
        # functions of their index — rebuilding consumed indices is
        # side-effect-free.
        k_sup = 4
        t0 = time.perf_counter()
        for i in range(k_sup):
            feeder._build_batch_inner(i)
        host_img_s = args.batch * k_sup / (time.perf_counter() - t0)
    except resilience.NumericAnomalyError as e:
        # mirror cli.cmd_train: exit 88 so a supervisor above applies
        # the rewind policy instead
        # of treating the divergence as a generic crash
        print(f"e2e-lmdb-train: {e}; exiting {resilience.EXIT_NUMERIC}",
              file=sys.stderr)
        return resilience.EXIT_NUMERIC
    finally:
        # failure paths must not leave prefetch workers holding the DB
        feeder.close()
        test_feeder.close()
        solver.close()
    img_s = args.batch * args.iters / dt

    device = jax.devices()[0]
    peak = peak_flops(device)
    flops = train_flops_per_image(solver.net) * img_s
    mfu = f"{flops / peak:.1%}" if peak else "n/a"
    guard_line = ""
    if sp.train_guard:
        guard_line = (f", guard: {solver.skipped_steps} skipped_steps, "
                      f"{guard_syncs} guard_syncs")
    print(f"e2e-lmdb-train: {img_s:.1f} img/s (b{args.batch}, "
          f"{args.iters} iters, {device.device_kind}, MFU {mfu}, "
          f"{dispatches} dispatches for "
          f"{args.iters} iters{eval_line}{guard_line}) — full host "
          "pipeline: LMDB read -> crc verify -> decode -> "
          "transform/staging (prefetched in worker threads) -> one "
          "train step an iteration with non-finite guard; "
          "eval passes fused+async (ISSUE 2)")

    # ISSUE 10 ingest report: decode-plane counters + both sides of the
    # feeding equation, printed AND journaled into the run JSON
    from caffe_mpi_tpu.data import decode as _decode
    ingest = _decode.STATS.snapshot()
    ingest.update({
        "codec": args.codec,
        "host_img_s": round(host_img_s, 1),
        "train_img_s": round(img_s, 1),
        "host_feeds_train": bool(host_img_s >= img_s),
    })
    native_decodes = ingest["native_records"] + ingest["fused_records"]
    resilience.write_run_manifest(sp.snapshot_prefix, kind="e2e_ingest",
                                  iteration=solver.iter, ingest=ingest)
    import json
    print("e2e-ingest: " + json.dumps(ingest))
    verdict = ("OK — host outruns the chip" if host_img_s >= img_s
               else "HOST-BOUND")
    print(f"e2e-ingest: host pipeline supplies {host_img_s:.0f} img/s vs "
          f"train consuming {img_s:.0f} img/s ({verdict}; "
          f"{native_decodes} native decodes, "
          f"{ingest['pil_records']} PIL)")
    if args.require_native_decode and native_decodes == 0:
        print("e2e-ingest: FAIL — native decode plane never engaged "
              "(--require-native-decode)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
