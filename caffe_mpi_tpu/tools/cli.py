"""caffe CLI — train / test / time / device_query / serve.

Reference: tools/caffe.cpp (499 LoC): command registry, gflags (-solver,
-model, -gpu, -snapshot, -weights, -iterations, -sigint_effect,
-sighup_effect), signal handling (SIGINT->stop, SIGHUP->snapshot), per-layer
timing benchmark (`caffe time`, tools/caffe.cpp:328-445).

Usage (gflags-compatible single-dash long flags accepted):
    python -m caffe_mpi_tpu.tools.cli train -solver solver.prototxt [-weights w.caffemodel | -snapshot s.solverstate] [-gpu all]
    python -m caffe_mpi_tpu.tools.cli test -model net.prototxt -weights w.caffemodel -iterations 50
    python -m caffe_mpi_tpu.tools.cli time -model net.prototxt -iterations 50
    python -m caffe_mpi_tpu.tools.cli device_query
    python -m caffe_mpi_tpu.tools.cli serve -model deploy.prototxt -weights w.caffemodel [-port 5000] [-smoke N] [-serve_queue_limit Q] [-serve_deadline_ms D] [-serve_stall_s S] [-serve_decoded_cache_mb M] [-serve_program_bank DIR [-require_bank_warm]] [-watch SNAPSHOT_PREFIX] [-replicas N [-serve_retry_budget R] [-replica_deadline S] [-fleet_dir D]]
"""

from __future__ import annotations

import argparse
import contextlib
import logging
import os
import signal
import sys
import time

import numpy as np

log = logging.getLogger("caffe")


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="caffe", description=__doc__)
    p.add_argument("command",
                   choices=["train", "test", "time", "device_query",
                            "serve"])
    for flag, kw in [
        ("solver", dict(default="", help="solver prototxt")),
        ("model", dict(default="", help="net prototxt")),
        ("weights", dict(default="", help=".caffemodel[.h5] to load")),
        ("snapshot", dict(default="", help=".solverstate[.h5|.npz] to resume")),
        ("gpu", dict(default="", help="'all' = full device mesh, or index")),
        ("mesh", dict(default="", help="explicit mesh shape, e.g. "
                      "'data=4,model=2'; layers with param_sharding "
                      "rules go tensor-parallel over 'model'")),
        ("gpipe", dict(type=int, default=0,
                       help="pipeline-train across S stages (heterogeneous "
                       "MPMD GPipe): net auto-cut into S device-pinned "
                       "stages, batch split into micro-batches, stage-local "
                       "optimizer updates; exclusive of -gpu/-mesh")),
        ("gpipe_micro", dict(type=int, default=0,
                             help="micro-batches per iteration under "
                             "-gpipe (default: number of stages)")),
        ("iterations", dict(type=int, default=50)),
        ("sigint_effect", dict(default="stop", choices=["stop", "snapshot", "none"])),
        ("sighup_effect", dict(default="snapshot", choices=["stop", "snapshot", "none"])),
        ("phase", dict(default="TEST", choices=["TRAIN", "TEST"])),
        ("synthetic", dict(action="store_true",
                           help="feed random data into Input layers")),
        ("profile", dict(default="", help="train, time: write a JAX/XLA "
                         "profiler trace (xplane, for XProf and "
                         "benchmarks/span_reduce.py) to this directory and "
                         "print its path. train traces ONE slice of the "
                         "run, drained before it starts and before it "
                         "stops: the second display interval, iterations "
                         "[D, 2D) counted from where this run starts (D = "
                         "the solver's display, 20 when that is 0); a run "
                         "shorter than 2D traces its second half. time "
                         "traces its whole-graph passes")),
        ("max_iter", dict(type=int, default=0,
                          help="override solver max_iter (0 = prototxt)")),
        ("test_iter", dict(type=int, default=0,
                           help="override solver test_iter (0 = prototxt)")),
    ]:
        p.add_argument(f"-{flag}", f"--{flag}", **kw)
    p.add_argument("-step_chunk", "--step_chunk", "--step-chunk",
                   dest="step_chunk", type=int, default=0,
                   help="fuse K iterations into ONE on-device lax.scan "
                   "dispatch (train only; overrides solver step_chunk; "
                   "0 = prototxt value, which defaults to 1). Chunks "
                   "auto-align to display/test_interval/snapshot "
                   "boundaries, so observable behavior is unchanged")
    p.add_argument("-test_chunk", "--test_chunk", "--test-chunk",
                   dest="test_chunk", type=int, default=0,
                   help="fuse T test batches into one evaluation "
                   "dispatch: the test pass runs as a jitted lax.scan "
                   "over a [T, B, ...] super-batch, ceil(test_iter/T) "
                   "dispatches per pass, overlapped with training "
                   "(overrides solver test_chunk; 0 = prototxt value, "
                   "which defaults to auto-sizing T from the eval "
                   "super-batch HBM budget)")
    # overlapped bucketed reduction flags (ISSUE 6, parallel/reduction.py)
    p.add_argument("-reduce_overlap", "--reduce-overlap",
                   dest="reduce_overlap", action="store_true",
                   help="explicit overlapped bucketed gradient "
                   "reduction: the data-parallel step computes grads "
                   "per device (shard_map) and psums them one bucket "
                   "at a time in reverse layer order, so the TPU "
                   "scheduler overlaps each bucket's collective with "
                   "the remaining backward (enables solver "
                   "reduce_overlap; requires -gpu all or -mesh; "
                   "incompatible nets fall back to the implicit "
                   "GSPMD reduction with a warning)")
    p.add_argument("-reduce_buckets", "--reduce-buckets",
                   dest="reduce_buckets", type=int, default=0,
                   help="gradient buckets for -reduce_overlap "
                   "(overrides solver reduce_buckets; 0 = prototxt "
                   "value, which defaults to the net-level "
                   "reduce_buckets, reference default 6); 0/negative "
                   "explicit values are rejected")
    p.add_argument("-grad_bucket_mb", "--grad-bucket-mb",
                   dest="grad_bucket_mb", type=float, default=0.0,
                   help="size -reduce_overlap buckets by a MiB budget "
                   "instead of a count (overrides solver "
                   "grad_bucket_mb; a single param above the budget "
                   "gets its own bucket with a warning; exclusive of "
                   "-reduce_buckets)")
    # mixed-precision flags (ISSUE 9, docs/benchmarks.md
    # "Mixed-precision bf16 training")
    p.add_argument("-precision", "--precision", default="",
                   choices=["", "f32", "bf16"],
                   help="train: compute precision (overrides solver "
                   "precision; '' = prototxt value, default f32 = "
                   "bitwise today). bf16 computes activations/gradients "
                   "in bfloat16 with f32 MASTER params and momentum — "
                   "updates in f32, reduce_overlap buckets psum in bf16 "
                   "(half the collective bytes), loss scaling armed per "
                   "-loss_scale")
    p.add_argument("-loss_scale", "--loss-scale", dest="loss_scale",
                   type=float, default=-1.0,
                   help="bf16 loss scale: 0 = DYNAMIC (scale rides the "
                   "train-scan carry; an overflow step is skipped and "
                   "the scale halves instead of exiting 88, regrowing "
                   "2x after loss_scale_window clean steps); > 0 = that "
                   "static scale (overrides solver loss_scale; -1 = "
                   "prototxt value, which defaults to dynamic). "
                   "Consumed only under -precision bf16")
    p.add_argument("-loss_scale_window", "--loss-scale-window",
                   dest="loss_scale_window", type=int, default=0,
                   help="clean steps before the dynamic loss scale "
                   "grows 2x (overrides solver loss_scale_window; 0 = "
                   "prototxt value, which defaults to 200)")
    # ingestion flags (ISSUE 10, docs/benchmarks.md "Ingestion")
    p.add_argument("-decoded_cache_mb", "--decoded-cache-mb",
                   dest="decoded_cache_mb", type=float, default=0.0,
                   help="train: RAM budget (MiB) for the bounded "
                   "decoded-record cache — post-decode pre-augment "
                   "uint8 records kept across epochs so the cached "
                   "span skips DB read + crc + JPEG/PNG decode after "
                   "epoch 1 (overrides solver decoded_cache_mb; 0 = "
                   "prototxt value, default off). The companion env "
                   "CAFFE_NATIVE_DECODE=0/1 forces the PIL/native "
                   "decoder for A/B runs")
    # survivable-training flags (ISSUE 3, utils/resilience.py)
    p.add_argument("-resume", "--resume", default="",
                   help="'auto' = resume from the newest VERIFIED "
                   "snapshot under the solver's snapshot_prefix (crc32c "
                   "manifest scan + run-manifest journal; corrupt "
                   "snapshots fall back to the newest prior verified "
                   "one; no snapshot = fresh start). A path behaves "
                   "like -snapshot")
    p.add_argument("-max_restarts", "--max-restarts", dest="max_restarts",
                   type=int, default=0,
                   help="supervised training: run the train loop in a "
                   "contained child process and restart it (with "
                   "--resume auto, exponential backoff) up to N times "
                   "on failure — including watchdog hard-exits. 0 "
                   "(default) = unsupervised, today's behavior")
    p.add_argument("-watchdog_deadline", "--watchdog-deadline",
                   dest="watchdog_deadline", type=float, default=0.0,
                   help="arm the dispatch watchdog: journal run state "
                   "and hard-exit (code 86) when any device dispatch/"
                   "harvest blocks longer than this many seconds "
                   "(overrides solver watchdog_deadline; 0 = prototxt "
                   "value, which defaults to off). Must exceed the "
                   "worst jit-compile time")
    p.add_argument("-snapshot_prefix", "--snapshot-prefix",
                   dest="snapshot_prefix", default="",
                   help="override solver snapshot_prefix")
    p.add_argument("-snapshot_every", "--snapshot-every",
                   dest="snapshot_every", type=int, default=0,
                   help="override solver snapshot interval "
                   "(0 = prototxt value)")
    p.add_argument("-snapshot_keep", "--snapshot-keep",
                   dest="snapshot_keep", type=int, default=0,
                   help="keep only the newest N snapshots, GC'ing older "
                   "ones after each write — never the newest verified "
                   "one (overrides solver snapshot_keep; 0 = prototxt "
                   "value, which defaults to keep-everything)")
    # elastic multi-host flags (ISSUE 11, docs/robustness.md
    # "Multi-host elasticity")
    p.add_argument("-hosts", "--hosts", type=int, default=0,
                   help="train: number of host processes in the "
                   "cluster (the reference's mpirun -n). > 1 "
                   "initializes jax.distributed against -coordinator "
                   "(bounded retry/backoff; a missing coordinator "
                   "journals and exits 87, never hangs), spans the "
                   "device mesh across every host, and stripes Feeder "
                   "records per host (overrides solver hosts; 0 = "
                   "prototxt value, default single-process). Env "
                   "fallbacks: CAFFE_TPU_NUM_HOSTS / "
                   "CAFFE_TPU_COORDINATOR / CAFFE_TPU_HOST_ID")
    p.add_argument("-coordinator", "--coordinator", default="",
                   help="train: host:port of host 0's coordination "
                   "service (required with -hosts > 1; overrides "
                   "solver coordinator)")
    p.add_argument("-host_id", "--host-id", dest="host_id", type=int,
                   default=-1,
                   help="train: this process's host index in "
                   "[0, hosts) (-1 = CAFFE_TPU_HOST_ID env)")
    p.add_argument("-host_deadline", "--host-deadline",
                   dest="host_deadline", type=float, default=0.0,
                   help="train: cross-host heartbeat deadline in "
                   "seconds — a peer host silent this long is "
                   "journaled to <prefix>.run.json and this worker "
                   "exits 87 (EXIT_CLUSTER) for the supervisor's "
                   "coordinated restart, instead of hanging inside "
                   "the next collective (overrides solver "
                   "host_deadline; 0 = prototxt value, default off)")
    p.add_argument("-min_hosts", "--min-hosts", dest="min_hosts",
                   type=int, default=0,
                   help="train: degraded-mode quorum floor (ISSUE 19, "
                   "needs -hosts > 1 and -max_restarts). After a "
                   "PERMANENT host loss the surviving supervisors run "
                   "the generation protocol: the lowest survivor "
                   "publishes a remapped generation with world W' >= "
                   "min_hosts and training continues at W' from the "
                   "last verified snapshot; a revived host parks and "
                   "is re-admitted at the next snapshot boundary "
                   "(overrides solver min_hosts; 0 = prototxt value, "
                   "default off = today's restart-all semantics)")
    # self-healing flags (ISSUE 4, docs/robustness.md)
    p.add_argument("-train_guard", "--train-guard", dest="train_guard",
                   action="store_true",
                   help="arm the on-device non-finite guard: a NaN/Inf "
                   "loss or gradient skips the optimizer update for "
                   "that step (params/momentum/BN unchanged) instead "
                   "of poisoning the weights; guard_max_skips "
                   "consecutive skips journals the anomaly and exits "
                   "88 for the supervisor to rewind (enables solver "
                   "train_guard; off by default = bitwise today)")
    p.add_argument("-guard_max_skips", "--guard-max-skips",
                   dest="guard_max_skips", type=int, default=-1,
                   help="consecutive skipped steps before exit 88; "
                   "0 = never exit, skip forever (overrides solver "
                   "guard_max_skips; -1 = prototxt value, which "
                   "defaults to 3)")
    p.add_argument("-anomaly_action", "--anomaly-action",
                   dest="anomaly_action", default="",
                   choices=["", "rewind", "rewind_lr", "abort"],
                   help="supervisor policy on exit 88: rewind to the "
                   "newest verified snapshot (default), rewind_lr = "
                   "rewind with base_lr scaled by anomaly_lr_mult per "
                   "numeric restart, abort = no restart (overrides "
                   "solver anomaly_action)")
    p.add_argument("-lr_scale", "--lr-scale", dest="lr_scale",
                   type=float, default=1.0,
                   help="multiply the solver's base_lr (set by the "
                   "supervisor on rewind_lr restarts; compounded per "
                   "numeric restart)")
    # inference-serving flags (ISSUE 7, caffe_mpi_tpu/serving/)
    p.add_argument("-port", "--port", type=int, default=5000,
                   help="serve: HTTP port (0 picks an ephemeral port)")
    p.add_argument("-labels", "--labels", default="",
                   help="serve: class-label file, one label per line")
    p.add_argument("-image_root", "--image-root", dest="image_root",
                   default="",
                   help="serve: allow GET /classify_path under this "
                   "directory")
    p.add_argument("-serve_window_ms", "--serve-window-ms",
                   dest="serve_window_ms", type=float, default=-1.0,
                   help="serve: continuous-batching window in ms — a "
                   "batch dispatches when this long has passed since "
                   "its first request, or earlier when a full max "
                   "bucket is waiting (overrides ServingParameter "
                   "serve_window_ms; -1 = schema default 5 ms; 0 = "
                   "dispatch immediately)")
    p.add_argument("-serve_buckets", "--serve-buckets",
                   dest="serve_buckets", default="",
                   help="serve: explicit padded-batch bucket ladder, "
                   "comma-separated (e.g. '1,4,16') — every bucket is "
                   "AOT-compiled at model load so arrival-size "
                   "variance never recompiles (overrides "
                   "ServingParameter serve_buckets; default geometric "
                   "1,4,16,... up to the deploy batch)")
    p.add_argument("-serve_hbm_mb", "--serve-hbm-mb",
                   dest="serve_hbm_mb", type=float, default=-1.0,
                   help="serve: HBM budget (MiB) for device-resident "
                   "model weights; the least-recently-used model "
                   "spills to its host master copy when exceeded "
                   "(overrides ServingParameter serve_hbm_mb; -1 = "
                   "schema default 0 = unlimited)")
    p.add_argument("-serve_dtype", "--serve-dtype", dest="serve_dtype",
                   default="", choices=["", "f32", "bf16"],
                   help="serve: bucket-program compute precision "
                   "(overrides ServingParameter serve_dtype; '' = "
                   "schema default f32). bf16 runs every bucket forward "
                   "in bfloat16 and casts scores back to f32 — the "
                   "ladder still AOT-compiles once per bucket, zero "
                   "steady-state compiles either way")
    p.add_argument("-smoke", "--smoke", type=int, default=0,
                   help="serve: self-test — serve N synthetic requests "
                   "of mixed sizes over real HTTP, print the telemetry "
                   "JSON (p50/p99/img_s/compile_count), assert zero "
                   "post-warmup compiles, and exit")
    # serving resilience flags (ISSUE 12, docs/serving.md 'Resilience')
    p.add_argument("-serve_queue_limit", "--serve-queue-limit",
                   dest="serve_queue_limit", type=int, default=-1,
                   help="serve: load-shedding admission control — a "
                   "submit arriving with this many requests already "
                   "backlogged fails fast with HTTP 429 instead of "
                   "queueing unboundedly (overrides ServingParameter "
                   "serve_queue_limit; -1 = schema default 0 = "
                   "unbounded)")
    p.add_argument("-serve_deadline_ms", "--serve-deadline-ms",
                   dest="serve_deadline_ms", type=float, default=-1.0,
                   help="serve: per-request dispatch deadline — a "
                   "request whose batch cannot dispatch this soon "
                   "after arrival fails with HTTP 504 at window close "
                   "(overrides ServingParameter serve_deadline_ms; "
                   "-1 = schema default 0 = no deadline)")
    p.add_argument("-serve_stall_s", "--serve-stall-s",
                   dest="serve_stall_s", type=float, default=-1.0,
                   help="serve: dispatch stall breaker — a device call "
                   "blocked this many seconds fails the "
                   "in-flight futures, journals, flips /healthz to 503 "
                   "and sheds new requests until a recovery probe "
                   "succeeds (overrides ServingParameter serve_stall_s; "
                   "-1 = schema default 0 = breaker off)")
    p.add_argument("-require_native_ingest", "--require-native-ingest",
                   dest="require_native_ingest", action="store_true",
                   help="serve -smoke: fail unless the HTTP leg's "
                   "requests actually decoded natively and preprocessed "
                   "through the window-fused plane (chip_smoke.py's "
                   "serve leg — a silent PIL fallback on hardware "
                   "would invalidate the serving ingest numbers)")
    p.add_argument("-serve_decoded_cache_mb", "--serve-decoded-cache-mb",
                   dest="serve_decoded_cache_mb", type=float, default=-1.0,
                   help="serve: hot-content decoded-request cache budget "
                   "in MiB — decoded uploads are kept in RAM keyed by "
                   "the crc32c of their encoded bytes (LRU), so repeated "
                   "hot images skip JPEG/PNG decode entirely (overrides "
                   "ServingParameter serve_decoded_cache_mb; -1 = schema "
                   "default 0 = cache off)")
    p.add_argument("-serve_program_bank", "--serve-program-bank",
                   dest="serve_program_bank", default="",
                   help="serve: persistent AOT program bank directory "
                   "(ISSUE 17) — each warmed bucket executable is "
                   "serialized there under a verified-atomic crc32c "
                   "manifest, and a bank-warm restart deserializes the "
                   "whole ladder with ZERO compiles (compile_count == "
                   "bank_misses; torn/stale entries recompile and "
                   "repopulate). Overrides ServingParameter "
                   "serve_program_bank; '' = schema default = bank off")
    p.add_argument("-require_bank_warm", "--require-bank-warm",
                   dest="require_bank_warm", action="store_true",
                   help="serve -smoke: fail unless the whole ladder "
                   "loaded from the program bank with zero compiles "
                   "(a silent recompile on hardware would invalidate "
                   "the zero-compile cold-start claim)")
    # serving-fleet flags (ISSUE 18, docs/serving.md 'Fleet')
    p.add_argument("-replicas", "--replicas", dest="serve_replicas",
                   type=int, default=-1,
                   help="serve: run N ServingEngine replica PROCESSES "
                   "behind a least-loaded typed-retry router with "
                   "heartbeat replica supervision and rolling -watch "
                   "swaps (sets ServingParameter serve_replicas; -1 = "
                   "schema default 0 = classic single-process serving)")
    p.add_argument("-serve_retry_budget", "--serve-retry-budget",
                   dest="serve_retry_budget", type=int, default=-1,
                   help="serve -replicas: how many sibling replicas a "
                   "typed-retryable failure (429 shed, 503 unhealthy, "
                   "dead-replica connection error) is retried on before "
                   "going typed to the client; 504/400 never retry "
                   "(overrides ServingParameter serve_retry_budget; "
                   "-1 = schema default 1)")
    p.add_argument("-replica_deadline", "--replica-deadline",
                   dest="replica_deadline", type=float, default=-1.0,
                   help="serve -replicas: replica heartbeat deadline in "
                   "seconds — one silent this long is drained from "
                   "rotation, journaled replica_dead, respawned "
                   "bank-warm, and re-admitted after its readyz gate "
                   "(overrides ServingParameter replica_deadline; -1 = "
                   "schema default 5 s)")
    p.add_argument("-fleet_dir", "--fleet-dir", dest="fleet_dir",
                   default="",
                   help="serve -replicas: fleet state directory "
                   "(heartbeats, staged swap weights, shared program "
                   "bank, replica logs, run journal); default "
                   "<model>_fleet. Also marks a spawned replica's own "
                   "process together with -replica_id (internal)")
    p.add_argument("-replica_id", "--replica-id", dest="replica_id",
                   type=int, default=-1,
                   help="internal: this process IS fleet replica K — "
                   "publish heartbeats under -fleet_dir and mount the "
                   "admin POST /swap route (set by FleetSupervisor, "
                   "not by operators)")
    p.add_argument("-watch", "--watch", dest="serve_watch", default="",
                   help="serve: snapshot prefix to tail for verified "
                   "hot-swaps — each newly crc32c-verified snapshot is "
                   "canary-gated and live-reloaded into the serving "
                   "model with zero recompiles; rejects (corrupt bytes, "
                   "non-finite canary) are journaled and the previous "
                   "weights keep serving")
    return p


def _select_mesh(gpu_flag: str, mesh_flag: str = ""):
    """-gpu all => data-parallel mesh over every device (the reference
    spawns one P2PSync per GPU; here one SPMD program).
    -mesh data=N,model=M => explicit 2D mesh: batch sharded over 'data',
    layers with `param_sharding` prototxt rules tensor-parallel over
    'model' — the one-command analogue of the reference's
    `mpirun -n N caffe train` line (README.md:40), generalized beyond DP."""
    from ..parallel import MeshPlan
    if mesh_flag:
        shape = {"data": 1, "model": 1}
        for kv in mesh_flag.split(","):
            k, _, v = kv.partition("=")
            k = k.strip()
            if k not in shape or not v.strip().isdigit():
                raise SystemExit(
                    f"bad -mesh entry {kv!r}: expected data=N[,model=M]")
            shape[k] = int(v)
        return MeshPlan.from_shape(shape["data"], shape["model"])
    if gpu_flag == "all":
        return MeshPlan.data_parallel()
    return None


def _synthetic_feed(net, seed=0):
    """Random feeds shaped from the net's Input layers (the reference's
    `caffe time` uses dummy data the same way). Integer feeds are chosen
    by CONSUMER, not by blob name: a blob eaten by Embed gets token ids in
    [0, input_dim); the target bottom of a classification loss/accuracy
    gets class ids."""
    import jax.numpy as jnp
    from ..utils.model_shapes import _CLASSIFICATION_CONSUMERS
    r = np.random.RandomState(seed)
    int_range: dict[str, int] = {}
    for layer in net.layers:
        lp = layer.lp
        if lp.type == "Embed" and lp.bottom:
            int_range[lp.bottom[0]] = lp.embed_param.input_dim
        elif lp.type in _CLASSIFICATION_CONSUMERS and len(lp.bottom) > 1:
            # one consumer table shared with utils.model_shapes.label_tops
            # so the two integer-feed detectors cannot drift
            int_range.setdefault(lp.bottom[1], 10)
    feeds = {}
    for key, (shape, kind) in net.feed_specs.items():
        if kind == "uint8":
            feeds[key] = jnp.asarray(
                r.randint(0, 256, shape).astype(np.uint8))
        elif kind == "aug":
            # zeros = top-left crop, no mirror — always valid offsets
            feeds[key] = jnp.zeros(shape, jnp.int32)
        elif key in int_range or kind == "int":
            feeds[key] = jnp.asarray(
                r.randint(0, max(int_range.get(key, 10), 1), shape))
        else:
            feeds[key] = jnp.asarray(r.randn(*shape).astype(np.float32))
    return feeds


def _build_feeders(net, phase, rank=0, world=1, model_dir="",
                   solver_param=None):
    """Create a Feeder per DB-backed data layer, or None for Input nets.
    solver_param supplies run-level ingestion knobs (decoded_cache_mb)."""
    from ..data import feeder_from_layer
    from ..data.feeder import HDF5Feeder
    model_dir = model_dir or getattr(net, "model_dir", "")
    for layer in net.layers:
        if layer.lp.type in ("Data", "ImageData"):
            return feeder_from_layer(
                layer.lp, phase, rank=rank, world=world, model_dir=model_dir,
                device_transform=getattr(layer, "dev_transform", False),
                solver_param=solver_param)
        if layer.lp.type == "HDF5Data":
            return HDF5Feeder(layer.lp, rank=rank, world=world,
                              model_dir=model_dir)
        if layer.lp.type == "WindowData":
            from ..data.window import WindowFeeder
            return WindowFeeder(layer.lp, phase, model_dir=model_dir,
                                rank=rank, world=world)
    return None


def _strip_flags(argv: list[str], flags: tuple[str, ...],
                 with_value: bool = True) -> list[str]:
    """Remove `flags` (and their values / `=`-joined spellings) from a
    child argv — the supervisor rewrites these per attempt/generation."""
    out, skip = [], False
    for tok in argv:
        if skip:
            skip = False
            continue
        if tok in flags:
            skip = with_value
            continue
        if tok.startswith(tuple(f + "=" for f in flags)):
            continue
        out.append(tok)
    return out


def _supervised_train(args) -> int:
    """Supervisor half of `train --max-restarts N`: run the actual
    training loop in a contained child process (own process group,
    killpg'd on every supervisor exit path) and restart it from the
    newest verified snapshot with exponential backoff when it dies —
    watchdog hard-exits (code 86) included. The crash-loop guard stops
    after N restarts with the per-attempt record in
    `<snapshot_prefix>.failures.log`.

    With `min_hosts` set on a multi-host run (ISSUE 19) the supervisor
    is the ELASTIC one (resilience.supervise_elastic): child failures
    run the generation protocol over the shared `<prefix>.cluster/`
    directory, and each generation's child argv is rewritten to the
    remapped `-hosts W' -host_id k' -coordinator <epoch>`."""
    import os
    from ..proto import SolverParameter
    from ..utils import resilience

    argv = list(getattr(args, "_argv", None) or sys.argv[1:])
    # strip the supervision flag from the child's argv (the env marker
    # below is the belt-and-braces recursion stop)
    child_argv = _strip_flags(
        argv, ("-max_restarts", "--max-restarts", "--max_restarts"))
    sp = SolverParameter.from_file(args.solver)
    prefix = args.snapshot_prefix or sp.snapshot_prefix or "snapshot"
    env = dict(os.environ, CAFFE_SUPERVISED_CHILD="1")
    anomaly_action = (args.anomaly_action or sp.anomaly_action
                      or "rewind")

    # degraded-mode elasticity (ISSUE 19): the generation protocol
    # engages only when the operator set the quorum floor on a real
    # multi-host launch — anything else is the classic supervisor,
    # bitwise
    min_hosts = args.min_hosts or getattr(sp, "min_hosts", 0)
    world = args.hosts or sp.hosts \
        or int(os.environ.get("CAFFE_TPU_NUM_HOSTS", "0") or 0)
    host_id = args.host_id if args.host_id >= 0 \
        else int(os.environ.get("CAFFE_TPU_HOST_ID", "-1") or -1)
    coordinator = args.coordinator or sp.coordinator \
        or os.environ.get("CAFFE_TPU_COORDINATOR", "")
    if min_hosts > 0 and world > 1 and host_id >= 0:
        host_deadline = args.host_deadline or sp.host_deadline or 5.0
        # the address peers reach THIS host at (the publisher of a new
        # generation hosts the next coordination-service epoch):
        # CAFFE_TPU_HOST_ADDR when the operator set it, else the
        # original coordinator's host part (exact for host 0 and for
        # single-machine smokes; multi-machine operators set the env)
        coord_host = os.environ.get("CAFFE_TPU_HOST_ADDR", "") or (
            coordinator.rsplit(":", 1)[0] if ":" in coordinator
            else "127.0.0.1")
        cluster_flags = ("-hosts", "--hosts", "-host_id", "--host-id",
                         "--host_id", "-coordinator", "--coordinator",
                         "-resume", "--resume")
        stable_argv = _strip_flags(child_argv, cluster_flags)

        def build_cmd(gen: dict, rank: int, resume: bool) -> list[str]:
            cmd = [sys.executable, "-m", "caffe_mpi_tpu.tools.cli"] \
                + stable_argv + ["-hosts", str(gen["world"]),
                                 "-host_id", str(rank)]
            if gen["world"] > 1:
                cmd += ["-coordinator", gen["coordinator"]]
            if resume:
                cmd += ["-resume", "auto"]
            return cmd

        journal = prefix if host_id == 0 else f"{prefix}.r{host_id}"
        return resilience.supervise_elastic(
            build_cmd, prefix=prefix, host_id=host_id,
            world_full=world, min_hosts=min_hosts,
            host_deadline=host_deadline, coordinator_host=coord_host,
            coordinator=coordinator, max_restarts=args.max_restarts,
            failure_log=journal + ".failures.log", env=env,
            anomaly_action=anomaly_action,
            anomaly_lr_mult=sp.anomaly_lr_mult)

    base_cmd = [sys.executable, "-m", "caffe_mpi_tpu.tools.cli"] + child_argv
    resume_cmd = base_cmd
    if not any(t in ("-resume", "--resume") or
               t.startswith(("-resume=", "--resume="))
               for t in child_argv):
        resume_cmd = base_cmd + ["-resume", "auto"]
    # fast-fail doomed formation (ISSUE 19): point the supervisor at
    # this host's cluster journal so repeated cluster_init_failed
    # records stop the restart loop early (single-host journals never
    # record that reason, so the param is inert there)
    journal = prefix if host_id <= 0 else f"{prefix}.r{host_id}"
    return resilience.supervise(
        base_cmd, resume_cmd, args.max_restarts,
        failure_log=prefix + ".failures.log", env=env,
        anomaly_action=anomaly_action,
        anomaly_lr_mult=sp.anomaly_lr_mult,
        journal_prefix=journal)


class _ProfileSlice:
    """`caffe train -profile DIR`: the profiler runs over one slice of the
    run, the rule of the flag's help text. `clip(chunk)` is called before
    every `solver.step(chunk)`: it starts or stops the session when the
    run stands on an edge of the slice, with the device drained so that
    the trace holds whole iterations, and cuts the chunk at the next edge."""

    def __init__(self, directory: str, solver, sp):
        self.directory, self.solver = directory, solver
        self.tracing = False
        n, interval = sp.max_iter - solver.iter, sp.display or 20
        lo = min(interval, n // 2)
        self.edges = [solver.iter + lo, solver.iter + min(lo + interval, n)]

    def clip(self, chunk: int) -> int:
        if not self.directory:
            return chunk
        import jax
        it = self.solver.iter
        if self.edges and it >= self.edges[0]:
            self.edges.pop(0)
            jax.block_until_ready(self.solver.params)
            if not self.tracing:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # spans, not every call
                jax.profiler.start_trace(self.directory,
                                         profiler_options=options)
                self.tracing = True
            else:
                self.stop()
        return min(chunk, self.edges[0] - it) if self.edges else chunk

    def stop(self) -> None:
        """Idempotent; also the error path's, so a failing run still
        leaves what it traced."""
        if not self.tracing:
            return
        import jax
        self.tracing = False
        jax.profiler.stop_trace()
        print(f"profiler trace written to {self.directory}", flush=True)


def _cluster_exit(prefix: str, rank: int, reason: str, error: str) -> int:
    """Journal a bounded cluster failure (ISSUE 11) and hand exit 87 to
    the supervisor. Rank 0 owns `<prefix>.run.json`; other ranks write
    their own `.r<k>` journal (same convention as the solver)."""
    from ..utils import resilience
    log.error("%s: %s; exiting %d for the supervisor's coordinated "
              "restart", reason, error, resilience.EXIT_CLUSTER)
    try:
        resilience.write_run_manifest(
            prefix if rank <= 0 else f"{prefix}.r{rank}",
            reason=reason, error=error,
            exit_code=resilience.EXIT_CLUSTER)
    except OSError:
        log.exception("cluster-failure journal failed (continuing)")
    return resilience.EXIT_CLUSTER


def _train_feeds(solver, sp, synthetic: bool):
    """(train feed, test feeds or None) of `cmd_train`; the train feed is
    None for an Input net without -synthetic."""
    # multi-host: each process reads its stripe of the global batch
    # (reference CursorManager record striping, data_reader.hpp:28-53)
    import jax as _jax
    feed_fn = _build_feeders(solver.net, "TRAIN",
                             rank=_jax.process_index(),
                             world=_jax.process_count(),
                             solver_param=sp)
    if feed_fn is None:
        if not synthetic:
            return None, None
        feeds = _synthetic_feed(solver.net)
        feed_fn = lambda it: feeds

    test_feed_fns = None
    if solver.test_nets:
        tf = []
        for tnet in solver.test_nets:
            # TEST feeders stripe per host exactly like TRAIN: the
            # eval path assembles each host's batch as a process-local
            # SHARD of the global test batch (shard_feeds), so
            # unstriped feeders would evaluate duplicate copies of
            # stripe 0 and never see the other hosts' records
            f = _build_feeders(tnet, "TEST",
                               rank=_jax.process_index(),
                               world=_jax.process_count(),
                               solver_param=sp)
            if f is None:
                feeds_t = _synthetic_feed(tnet, seed=1)
                tf.append(lambda it, feeds_t=feeds_t: feeds_t)
            else:
                tf.append(f)
        test_feed_fns = tf
    return feed_fn, test_feed_fns


def cmd_train(args) -> int:
    from ..proto import SolverParameter
    from ..utils import resilience
    if not args.solver:
        log.error("train requires -solver")
        return 1
    import os
    if args.max_restarts > 0 \
            and os.environ.get("CAFFE_SUPERVISED_CHILD") != "1":
        # the supervisor only launches children: it must stay off jax,
        # because a parent that has touched the backend holds the chip
        # its child needs
        return _supervised_train(args)
    from ..data.feeder import data_shape_probe
    from ..solver import Solver
    from ..utils import spans
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sp = SolverParameter.from_file(args.solver)
    if args.max_iter:
        sp.max_iter = args.max_iter
    if args.test_iter:
        sp.test_iter = [args.test_iter] * max(len(sp.test_iter), 1)
    if args.step_chunk:
        sp.step_chunk = args.step_chunk
    if args.test_chunk:
        sp.test_chunk = args.test_chunk
    if args.snapshot_prefix:
        sp.snapshot_prefix = args.snapshot_prefix
    if args.snapshot_every:
        sp.snapshot = args.snapshot_every
    if args.snapshot_keep:
        sp.snapshot_keep = args.snapshot_keep
    if args.watchdog_deadline:
        sp.watchdog_deadline = args.watchdog_deadline
    if args.reduce_overlap:
        sp.reduce_overlap = True
    # a CLI sizing mode overrides the prototxt's OTHER mode too (a
    # recipe with `reduce_buckets: 4` can be re-run under a CLI byte
    # budget without editing it); both CLI flags at once still reach
    # the solver's "not both" validation and fail loudly
    if args.reduce_buckets:
        sp.reduce_buckets = args.reduce_buckets
        if not args.grad_bucket_mb:
            sp.clear("grad_bucket_mb")
    if args.grad_bucket_mb:
        sp.grad_bucket_mb = args.grad_bucket_mb
        if not args.reduce_buckets:
            sp.clear("reduce_buckets")
    if getattr(sp, "reduce_overlap", False):
        # libtpu scheduling flags for collective/compute overlap —
        # LIBTPU_INIT_ARGS is read only by libtpu, so this is a no-op
        # on CPU runs; must land before the first jax computation
        # initializes the backend (reduction.tpu_overlap_flags)
        from ..parallel import reduction
        if reduction.apply_tpu_overlap_flags(os.environ):
            log.info("TPU overlap flags appended to LIBTPU_INIT_ARGS: %s",
                     " ".join(reduction.tpu_overlap_flags()))
    if args.decoded_cache_mb:
        sp.decoded_cache_mb = args.decoded_cache_mb
    if args.precision:
        sp.precision = args.precision
    if args.loss_scale >= 0:
        # 0 is meaningful (dynamic scaling); -1 = prototxt
        sp.loss_scale = args.loss_scale
    if args.loss_scale_window:
        sp.loss_scale_window = args.loss_scale_window
    if args.train_guard:
        sp.train_guard = True
    if args.guard_max_skips >= 0:
        # 0 is meaningful (never exit — skip forever); -1 = prototxt
        sp.guard_max_skips = args.guard_max_skips
    if args.lr_scale != 1.0:
        # rewind_lr restart: the supervisor scales the recipe's LR so
        # the replay does not step straight back into the divergence
        sp.base_lr = sp.base_lr * args.lr_scale
        log.info("base_lr scaled by %g -> %g (anomaly rewind)",
                 args.lr_scale, sp.base_lr)
    if args.hosts:
        sp.hosts = args.hosts
    if args.coordinator:
        sp.coordinator = args.coordinator
    if args.host_deadline:
        sp.host_deadline = args.host_deadline
    if args.min_hosts:
        sp.min_hosts = args.min_hosts

    # elastic multi-host bootstrap (ISSUE 11): form the jax.distributed
    # cluster BEFORE any jax device use, so the mesh below spans every
    # host. Cluster-formation failure is a bounded, journaled exit 87 —
    # the supervisor's coordinated restart re-forms the cluster.
    from ..parallel import mesh as mesh_mod
    journal_prefix = args.snapshot_prefix or sp.snapshot_prefix \
        or "snapshot"
    world, host_rank = 1, 0
    try:
        world, coordinator, host_rank = mesh_mod.resolve_cluster(
            sp, host_id=args.host_id)
        if world > 1:
            mesh_mod.init_distributed(coordinator, world, host_rank)
            if host_rank == 0:
                # degraded-mode elasticity (ISSUE 19): mirror the
                # generation record the elastic supervisor handed us
                # onto the KV store for in-band observability; no-op
                # outside a min_hosts run
                mesh_mod.publish_generation()
    except resilience.ClusterError as e:
        return _cluster_exit(journal_prefix, max(host_rank, 0),
                             "cluster_init_failed", str(e))
    model_dir = os.path.dirname(os.path.abspath(args.solver)) \
        if not (sp.net and os.path.exists(sp.net)) else ""
    gpipe_cfg = None
    if args.gpipe:
        # pipeline training from the train entrypoint, the way the
        # reference launches ITS parallelism (tools/caffe.cpp:223-225)
        if args.gpu or args.mesh:
            raise SystemExit("-gpipe is exclusive of -gpu/-mesh "
                             "(stages own whole devices)")
        gpipe_cfg = {"stages": args.gpipe, "micro": args.gpipe_micro}
    cluster_rank = 0
    if world > 1:
        import jax as _jax
        cluster_rank = _jax.process_index()
    solver = Solver(sp, mesh=_select_mesh(args.gpu, args.mesh),
                    model_dir=model_dir, gpipe=gpipe_cfg,
                    rank=cluster_rank,
                    data_shape_probe=lambda lp: data_shape_probe(lp, model_dir))
    if args.resume and args.resume != "auto":
        # a concrete path behaves like -snapshot
        args.snapshot = args.snapshot or args.resume
    resumed = None
    if args.resume == "auto":
        # newest verified snapshot (crc32c manifest scan); falls back
        # across corrupt snapshots; None = fresh start. The explicit
        # -snapshot/-weights flags only apply when auto found nothing.
        # Cluster runs must agree on ONE resume point (divergent picks
        # would deadlock the first collective): rank 0 scans and
        # publishes its decision on the coordination service; peers
        # restore exactly that snapshot.
        if world > 1 and cluster_rank > 0:
            # rank 0 crc-verifies (and may fall back across) whole
            # checkpoints before publishing — the wait must scale with
            # checkpoint size, not a fixed constant (env-tunable for
            # huge sharded sets); a dead service still returns fast
            peer = mesh_mod.cluster_kv_get(
                "caffe/resume_state",
                timeout_s=float(os.environ.get(
                    "CAFFE_TPU_RESUME_TIMEOUT", "600") or 600))
            if peer is None:
                return _cluster_exit(
                    journal_prefix, cluster_rank, "cluster_resume_failed",
                    "rank 0 never published its resume decision")
            if peer:
                try:
                    solver.restore(peer)
                except (resilience.SnapshotCorruptError, OSError) as e:
                    # shards not yet visible on this host (NFS lag) or
                    # local bitrot: a journaled 87 lets the supervisor
                    # retry the coordinated resume instead of an
                    # unjournaled crash with a generic exit code
                    return _cluster_exit(
                        journal_prefix, cluster_rank,
                        "cluster_resume_failed",
                        f"rank 0's snapshot {peer} failed to load "
                        f"here: {e}")
                resumed = peer
        else:
            resumed = solver.restore_auto()
            if world > 1 and not mesh_mod.cluster_kv_set(
                    "caffe/resume_state", resumed or ""):
                # peers are blocked waiting for this key; training on
                # alone would end in an unbounded first-collective hang
                # after they give up — the exact hang class ISSUE 11
                # exists to bound
                return _cluster_exit(
                    journal_prefix, cluster_rank, "cluster_resume_failed",
                    "could not publish the resume decision (dead "
                    "coordination service?)")
    if resumed is None:
        if args.snapshot:
            try:
                solver.restore(args.snapshot)
            except resilience.SnapshotCorruptError as e:
                if world > 1:
                    # a PER-HOST fallback scan could land ranks on
                    # divergent iterations and deadlock the first
                    # collective — journal + 87 so the supervisor
                    # retries the coordinated resume instead
                    return _cluster_exit(
                        journal_prefix, cluster_rank,
                        "cluster_resume_failed",
                        f"-snapshot {args.snapshot} corrupt on this "
                        f"host: {e}")
                log.warning("%s", e)
                resumed = solver.restore_auto()
                if resumed is None:
                    raise
                log.warning("resumed from %s instead of the corrupt %s",
                            resumed, args.snapshot)
        elif args.weights:
            for w in args.weights.split(","):
                solver.load_weights(w)

    # signal plumbing (reference SignalHandler, tools/caffe.cpp:209-211):
    # handlers only set flags; actions run at the iteration boundary —
    # snapshotting from inside the handler would race the jitted step's
    # donated buffers
    state = {"stop": False, "snap": False}

    def on_signal(effect):
        def handler(sig, frame):
            if effect == "snapshot":
                state["snap"] = True
            elif effect == "stop":
                state["stop"] = True
                log.info("signal: stopping after this iteration")
        return handler

    signal.signal(signal.SIGINT, on_signal(args.sigint_effect))
    if hasattr(signal, "SIGHUP"):
        signal.signal(signal.SIGHUP, on_signal(args.sighup_effect))

    with spans.phase("cli/feeders"):
        feed_fn, test_feed_fns = _train_feeds(solver, sp, args.synthetic)
    if feed_fn is None:
        log.error("net has no Data layer; pass -synthetic to train on "
                  "random data or use a Data/ImageData net")
        return 1

    # bind the quarantine journal next to the snapshots: corrupt
    # records the feeder substitutes during this run are audited in
    # <prefix>.quarantine.json (ISSUE 4; appends across supervised
    # restarts). Multi-host runs journal per host (.r<k>, ISSUE 11);
    # rank 0 merges them at snapshot time.
    # Across degraded-mode generations (ISSUE 19) a host's RANK moves
    # (remapped contiguous over the survivors) but its identity does
    # not: key the journal on the stable original host id the elastic
    # supervisor publishes, so quarantine attribution survives remaps.
    _stable_host = os.environ.get("CAFFE_TPU_CLUSTER_SELF")
    resilience.QUARANTINE.configure(resilience.quarantine_journal_path(
        sp.snapshot_prefix or "snapshot", rank=cluster_rank,
        world=world,
        host=int(_stable_host) if _stable_host else None))

    t0 = time.time()
    start_iter = solver.iter
    profile = _ProfileSlice(args.profile, solver, sp)
    # the first chunk traces, lowers and builds the step: the last phase
    # of start-up, after which the ledger's table is logged, once
    first = spans.phase("cli/first step")
    try:
        while solver.iter < sp.max_iter and not state["stop"]:
            chunk = profile.clip(min(100, sp.max_iter - solver.iter))
            with first or contextlib.nullcontext():
                solver.step(chunk, feed_fn, test_feed_fns)
            if first is not None:
                first = None
                log.info("%s", spans.ledger.table())
            if state["snap"]:
                state["snap"] = False
                solver.snapshot()
        profile.clip(0)  # a slice that ends with the run
        if not state["stop"] and test_feed_fns and sp.test_interval:
            # final evaluation after the last iteration. Deliberate
            # deviation: the reference only runs its trailing TestAll when
            # iter %% test_interval == 0 (solver.cpp:431); here it runs
            # unconditionally so every completed run reports final scores
            # — the examples parse this line to self-assert accuracy.
            solver.test_all(test_feed_fns)
        if (state["stop"] and args.sigint_effect == "stop") or (
                not state["stop"] and sp.snapshot_prefix
                and solver.should_snapshot_after_train()):
            solver.snapshot()  # reference snapshots at stop/after-train
            # (solver.cpp:402-407)
        if world > 1:
            # end-of-training barrier (ISSUE 11): hosts finish at
            # skewed times; rank 0's coordination service must not die
            # underneath a peer still mid-collective/KV-call. The
            # heartbeat keeps ticking while we wait here, so a peer
            # that CRASHED instead of arriving still becomes a bounded
            # exit-87 within host_deadline.
            if not mesh_mod.cluster_barrier("caffe_train_done"):
                return _cluster_exit(
                    journal_prefix, cluster_rank, "cluster_exit_failed",
                    "end-of-training barrier timed out (peer host "
                    "lost after training?)")
            # only NOW is departure clean — a farewell on a failure
            # path would stop peers monitoring a crashed host
            solver.heartbeat_farewell()
    except resilience.ClusterError as e:
        # a cluster operation inside training (sharded-snapshot write
        # barrier) failed in a bounded way — journal + 87, supervisor
        # restarts the whole cluster. The rejoin trigger (ISSUE 19)
        # rides the same exit with reason "cluster_rejoin" so the
        # elastic supervisor publishes the grow-back generation.
        return _cluster_exit(journal_prefix, cluster_rank,
                             getattr(e, "journal_reason", "cluster_lost"),
                             str(e))
    except resilience.NumericAnomalyError as e:
        # the solver already journaled the anomaly to <prefix>.run.json;
        # exit 88 routes the supervisor through anomaly_action
        # (rewind | rewind_lr | abort) instead of a plain crash restart
        log.error("%s; exiting %d for the supervisor to rewind", e,
                  resilience.EXIT_NUMERIC)
        return resilience.EXIT_NUMERIC
    finally:
        profile.stop()
        # async interval writes must land even when training raises —
        # a half-written checkpoint is worse than a slow exit — and the
        # fused-mode feed queue's worker thread must not outlive the run
        solver.close()
        # drain any debounced quarantine-journal tail: the audit must
        # be complete on every exit path
        resilience.QUARANTINE.flush()
    if world > 1:
        # past the exit barrier on every host: safe to drop the service
        mesh_mod.shutdown_distributed()
    elapsed = time.time() - t0
    imgs = (solver.iter - start_iter) * solver._batch_images() \
        * max(sp.iter_size, 1) * max(solver._gpipe_micro, 1)
    log.info("Optimization done: %d iters, %.1f s, %.1f img/s overall",
             solver.iter, elapsed, imgs / max(elapsed, 1e-9))
    return 0


def cmd_test(args) -> int:
    import jax
    from ..net import Net
    from ..proto import NetParameter
    from .. import io as caffe_io
    import os
    if not args.model:
        log.error("test requires -model")
        return 1
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    net = Net(NetParameter.from_file(args.model), phase="TEST",
              model_dir=os.path.dirname(os.path.abspath(args.model)))
    params, state = net.init(jax.random.PRNGKey(0))
    if args.weights:
        params, state = net.import_weights(params, state,
                                           caffe_io.load_weights(args.weights))
    feeder = _build_feeders(net, "TEST")
    import jax.numpy as jnp
    fwd = jax.jit(lambda p, s, f: net.apply(p, s, f, train=False)[0])
    consumed = {b for l in net.layers for b in l.lp.bottom}
    outputs = [t for l in net.layers for t in l.lp.top if t not in consumed]
    # per-batch score means stay ON DEVICE across the loop (tpulint
    # host-sync: a float() here would block on the device per iteration
    # per blob); the harvest happens after the last batch, and the
    # average itself is summed in float64 on the host exactly like the
    # per-iteration path used to — the perf fix must not change the
    # reported numerics
    totals: dict[str, list] = {b: [] for b in outputs}
    for it in range(args.iterations):
        feeds = feeder(it) if feeder else _synthetic_feed(net, seed=it)
        if feeder:
            feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        blobs = fwd(params, state, feeds)
        for b in outputs:
            totals[b].append(jnp.mean(blobs[b]))  # device scalar, async
    for b in outputs:
        # stack on device first: asarray over a python list of device
        # scalars would pull them one RTT at a time
        # lint: ok(host-sync) — harvest at exit: one bulk pull per blob
        avg = float(np.mean(np.asarray(jnp.stack(totals[b])),
                            dtype=np.float64))
        log.info("%s = %.5g", b, avg)
        print(f"{b} = {avg:.5g}")
    return 0


def cmd_time(args) -> int:
    """Per-layer forward/backward timing (reference tools/caffe.cpp:328-445).
    Per-layer costs come from timing each layer's jitted apply in isolation;
    whole-graph fwd and fwd+bwd are timed as single fused programs — the
    number that actually matters on TPU, where XLA fuses across layers."""
    import jax
    import jax.numpy as jnp
    from ..net import Net
    from ..proto import NetParameter
    if not args.model:
        log.error("time requires -model")
        return 1
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    net = Net(NetParameter.from_file(args.model), phase=args.phase)
    params, state = net.init(jax.random.PRNGKey(0))
    feeds = _synthetic_feed(net)

    # materialize every blob once to get per-layer inputs
    blobs, _, _ = net.apply(params, state, feeds, train=False)
    blobs = dict(blobs)
    rows = []
    iters = max(args.iterations, 1)
    def timeit(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    for layer in net.layers:
        from ..layers.data_layers import InputLayerBase
        if isinstance(layer, InputLayerBase):
            continue
        bottoms = [blobs[b] for b in layer.lp.bottom]
        lparams = net._layer_params(layer, params, False)
        lstate = state.get(layer.name, {})
        fn = jax.jit(lambda p, s, bs, layer=layer: layer.apply(
            p, s, bs, train=False, rng=None)[0])
        fwd_ms_l = timeit(fn, lparams, lstate, bottoms)
        # isolated backward: VJP wrt params+float bottoms (reference times
        # each layer's Backward the same way, tools/caffe.cpp:403-423)
        float_idx = [i for i, b in enumerate(bottoms)
                     if jnp.issubdtype(b.dtype, jnp.floating)]
        bwd_ms_l = float("nan")
        if lparams or float_idx:
            def scalar_fn(p, bs, layer=layer, lstate=lstate):
                tops, _ = layer.apply(p, lstate, bs, train=False, rng=None)
                return sum(jnp.sum(t.astype(jnp.float32) ** 2) for t in tops
                           if hasattr(t, "ndim"))
            bwd = jax.jit(jax.grad(scalar_fn, argnums=(0, 1),
                                   allow_int=True))
            try:
                bwd_ms_l = timeit(bwd, lparams, bottoms)
            except Exception:
                pass  # non-differentiable layer: report nan
        rows.append((layer.name, layer.lp.type, fwd_ms_l, bwd_ms_l))

    def whole(train):
        rng_key = jax.random.PRNGKey(0)

        def f(p, s, fd):
            out_blobs, _, loss = net.apply(p, s, fd, train=train,
                                           rng=rng_key if train else None)
            if train:
                return loss
            # eval: force every terminal blob so XLA can't DCE the net
            # when the TEST phase has no loss layer
            return sum(jnp.sum(b.astype(jnp.float32)) for b in
                       out_blobs.values() if hasattr(b, "ndim"))
        if train:
            g = jax.jit(jax.grad(f))
        else:
            g = jax.jit(f)
        # compiled-program memory accounting (replaces the reference's
        # hand-tallied per-net GPU byte report, net.cpp:386-400, with the
        # compiler's actual buffer assignment)
        try:
            mem = g.lower(params, state, feeds).compile().memory_analysis()
            if mem is not None:
                print(f"  [{'train' if train else 'eval'} program] "
                      f"temp {getattr(mem, 'temp_size_in_bytes', 0)/2**20:.1f} MiB, "
                      f"args {getattr(mem, 'argument_size_in_bytes', 0)/2**20:.1f} MiB, "
                      f"output {getattr(mem, 'output_size_in_bytes', 0)/2**20:.1f} MiB")
        except Exception:
            pass
        out = g(params, state, feeds)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(params, state, feeds)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    if args.profile:
        # TPU tracing parity (reference relies on `caffe time`+nvprof; here
        # the xplane trace opens in TensorBoard/XProf)
        with jax.profiler.trace(args.profile):
            fwd_ms = whole(False)
            total_ms = whole(True) if net.loss_blobs else float("nan")
        print(f"profiler trace written to {args.profile}")
    else:
        fwd_ms = whole(False)
        total_ms = whole(True) if net.loss_blobs else float("nan")
    # analytic model FLOPs + MFU (utils/flops.py; the efficiency metric
    # img/s can't express — how busy the MXU actually is)
    from ..utils.flops import (layer_macs_per_image, net_macs_per_image,
                               peak_flops, train_flops_per_image)
    batch = next((net.blob_shapes[b][0] for b in net.feed_blobs), 1)
    layer_gflops = {l.name: 2 * layer_macs_per_image(l) * batch / 1e9
                    for l in net.layers}
    print(f"{'layer':<28}{'type':<20}{'fwd ms':>12}{'bwd ms':>12}"
          f"{'GFLOPs':>10}  (isolated)")
    for name, tname, fms, bms in rows:
        bs = f"{bms:.3f}" if bms == bms else "-"
        gf = layer_gflops.get(name, 0.0)
        gfs = f"{gf:.2f}" if gf else "-"
        print(f"{name:<28}{tname:<20}{fms:>12.3f}{bs:>12}{gfs:>10}")
    print(f"\nwhole-graph forward (fused): {fwd_ms:.3f} ms")
    print(f"whole-graph forward+backward (fused): {total_ms:.3f} ms")
    print(f"sum of isolated per-layer fwd: {sum(r[2] for r in rows):.3f} ms "
          "(>= fused time; the gap is XLA fusion)")
    fwd_gflops = 2 * net_macs_per_image(net) * batch / 1e9
    print(f"model FLOPs: fwd {fwd_gflops:.2f} GFLOPs/batch "
          f"(batch {batch}); fwd+bwd "
          f"{train_flops_per_image(net) * batch / 1e9:.2f}")
    dev = jax.devices()[0]
    peak = peak_flops(dev)
    if fwd_ms == fwd_ms and fwd_ms > 0:
        achieved_f = fwd_gflops / fwd_ms  # GFLOP / ms = TFLOP/s
        line = f"achieved: fwd {achieved_f:.2f} TFLOP/s"
        if total_ms == total_ms and total_ms > 0:
            achieved_t = train_flops_per_image(net) * batch / 1e9 / total_ms
            line += f", fwd+bwd {achieved_t:.2f} TFLOP/s"
            if peak:
                line += (f"; MFU {achieved_t * 1e12 / peak:.1%} "
                         f"({dev.device_kind} peak {peak / 1e12:.0f} TFLOP/s)")
        print(line)
    return 0


def cmd_serve(args) -> int:
    """Production inference serving (ISSUE 7, caffe_mpi_tpu/serving/):
    load the deploy net into a ServingEngine — params device-resident,
    every padded batch bucket AOT-compiled NOW — and mount the stdlib
    HTTP front-end on it. `-smoke N` runs the self-test path instead of
    serving forever."""
    from ..proto.config import ServingParameter
    from ..serving import ServingEngine
    from ..serving.http_front import make_server
    if not args.model:
        log.error("serve requires -model (a deploy prototxt)")
        return 1
    sp = ServingParameter()
    if args.serve_window_ms >= 0:
        sp.serve_window_ms = args.serve_window_ms
    if args.serve_buckets:
        sp.serve_buckets = args.serve_buckets
    if args.serve_hbm_mb >= 0:
        sp.serve_hbm_mb = args.serve_hbm_mb
    if args.serve_dtype:
        sp.serve_dtype = args.serve_dtype
    if args.serve_queue_limit >= 0:
        sp.serve_queue_limit = args.serve_queue_limit
    if args.serve_deadline_ms >= 0:
        sp.serve_deadline_ms = args.serve_deadline_ms
    if args.serve_stall_s >= 0:
        sp.serve_stall_s = args.serve_stall_s
    if args.serve_decoded_cache_mb >= 0:
        sp.serve_decoded_cache_mb = args.serve_decoded_cache_mb
    if args.serve_program_bank:
        sp.serve_program_bank = args.serve_program_bank
    if args.serve_replicas >= 0:
        sp.serve_replicas = args.serve_replicas
    if args.serve_retry_budget >= 0:
        sp.serve_retry_budget = args.serve_retry_budget
    if args.replica_deadline >= 0:
        sp.replica_deadline = args.replica_deadline
    # fleet mode (ISSUE 18): N replica processes behind the typed-retry
    # router — this process becomes the router+supervisor and never
    # builds an engine itself
    if sp.serve_replicas >= 1 and args.replica_id < 0:
        return _serve_fleet(args, sp)
    replica_beat = None
    if args.replica_id >= 0 and args.fleet_dir:
        # this process IS fleet replica K: publish heartbeats so the
        # supervisor can mourn a silent death, and accept admin swaps
        from ..serving.fleet import ReplicaBeat
        replica_beat = ReplicaBeat(args.fleet_dir, args.replica_id,
                                   deadline=sp.replica_deadline)
        replica_beat.start()
    # serving run journal (<model>.serve.run.json): breaker trips, hot
    # swaps + rejections, shutdown — next to the deploy prototxt (fleet
    # replicas journal per-replica so siblings don't clobber each other)
    journal = os.path.splitext(args.model)[0]
    if args.replica_id >= 0:
        journal += f".r{args.replica_id}"
    engine = ServingEngine(sp, journal=journal)
    engine.load_model("default", args.model, args.weights or None)
    watcher = None
    if args.serve_watch:
        from ..serving.watch import SnapshotWatcher
        watcher = SnapshotWatcher(engine, "default", args.serve_watch)
        watcher.start()
    srv = make_server(engine, "default", labels=args.labels or None,
                      image_root=args.image_root or None,
                      port=args.port if not args.smoke else 0,
                      admin=replica_beat is not None)
    host, port = srv.server_address[:2]
    if not args.smoke:
        log.info("serving on http://%s:%s (model %s, buckets %s, "
                 "window %.1f ms)", host, port, args.model,
                 engine.model("default").fwd.ladder, engine.window_ms)
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            if watcher is not None:
                watcher.stop()
            if replica_beat is not None:
                replica_beat.stop()
            srv.shutdown()
            # graceful: stop accepting, flush the window, resolve every
            # in-flight future, then close (docs/serving.md Resilience)
            engine.shutdown()
        return 0
    try:
        return _serve_smoke(args, engine, srv)
    finally:
        if watcher is not None:
            watcher.stop()
        if replica_beat is not None:
            replica_beat.stop()


def _serve_fleet(args, sp) -> int:
    """`caffe serve -replicas N` (ISSUE 18, docs/serving.md "Fleet"):
    spawn N replica processes (each a full `caffe serve` with its own
    engine, bank-warmed from the shared program bank), supervise them
    by heartbeat, and mount the typed-retry router as the public HTTP
    surface. `-watch` tails snapshots ROUTER-side, so each verified
    snapshot canaries on one replica before rolling fleet-wide."""
    from ..serving.fleet import FleetSupervisor, make_router_server
    fleet_dir = args.fleet_dir or os.path.splitext(args.model)[0] + "_fleet"
    sup = FleetSupervisor(args.model, args.weights or "",
                          sp.serve_replicas, fleet_dir, serving_param=sp)
    log.info("fleet: spawning %d replicas under %s (bank %s, heartbeat "
             "deadline %.1fs, retry budget %d)", sp.serve_replicas,
             fleet_dir, sup.bank_dir, sup.deadline,
             sup.router.retry_budget)
    sup.start()
    watcher = None
    if args.serve_watch:
        from ..serving.watch import SnapshotWatcher
        watcher = SnapshotWatcher(sup.router, "default", args.serve_watch)
        watcher.start()
    srv = make_router_server(sup.router,
                             port=args.port if not args.smoke else 0)
    host, port = srv.server_address[:2]
    try:
        if not args.smoke:
            log.info("fleet router serving on http://%s:%s (%d replicas)",
                     host, port, sp.serve_replicas)
            try:
                srv.serve_forever()
            except KeyboardInterrupt:
                pass
            return 0
        return _fleet_smoke(args, sup, srv)
    finally:
        if watcher is not None:
            watcher.stop()
        srv.shutdown()
        sup.stop()


def _fleet_smoke(args, sup, srv) -> int:
    """`serve -replicas N -smoke M`: M synthetic PNG requests through
    the real router HTTP surface, then assert every request resolved
    typed, traffic spread across replicas, and every replica held the
    bank-extended zero-recompile invariant. The full replica-kill /
    rolling-swap proof lives in tools/fleet_smoke.py."""
    import io
    import json
    import threading
    import urllib.error
    import urllib.request
    from PIL import Image

    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    rng = np.random.RandomState(0)
    url = f"http://127.0.0.1:{srv.server_address[1]}/classify"
    ok_n = 0
    for _ in range(args.smoke):
        buf = io.BytesIO()
        Image.fromarray(rng.randint(0, 255, (32, 32, 3), np.uint8)
                        ).save(buf, format="PNG")
        req = urllib.request.Request(
            url, data=buf.getvalue(),
            headers={"Content-Type": "image/png"})
        try:
            json.loads(urllib.request.urlopen(req, timeout=60).read())
            ok_n += 1
        except urllib.error.HTTPError as e:
            # typed failures (429/503/504 with a kind) count as resolved
            doc = json.loads(e.read() or b"{}")
            if not doc.get("kind"):
                log.error("fleet smoke: UNTYPED failure %s: %s",
                          e.code, doc)
                return 1
    stats = sup.router.stats()
    print(json.dumps({"serve_fleet_smoke": stats}))
    spread = sum(1 for doc in stats["replicas"].values()
                 if doc.get("requests", 0) > 0)
    for rid, doc in stats["replicas"].items():
        if "error" in doc:
            log.error("fleet smoke: replica %s unreachable", rid)
            return 1
        bank = doc.get("bank", {})
        if doc.get("compile_count") != bank.get("misses") or \
                doc.get("compile_count", 0) + bank.get("hits", 0) \
                != doc.get("warmed_buckets"):
            log.error("fleet smoke: replica %s broke the zero-recompile "
                      "invariant: %s", rid, doc)
            return 1
    if ok_n == 0 or (args.smoke >= 8 and spread < 2
                     and stats["fleet"]["replicas"] > 1):
        log.error("fleet smoke: no spread (%d ok, %d replicas served)",
                  ok_n, spread)
        return 1
    return 0


def _serve_smoke(args, engine, srv) -> int:
    """`serve -smoke N`: fire N mixed-size synthetic requests — a few
    over real HTTP (the full decode->submit->future path), the rest
    straight into the engine — then print stats and verify the
    zero-recompile claim (chip_smoke.py's serve leg runs this on the
    chip)."""
    import json
    import threading
    import urllib.request

    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        model = engine.model("default")
        shape = model.fwd.input_shape()
        rng = np.random.RandomState(0)
        if len(shape) == 4:
            c, h, w = shape[1], shape[2], shape[3]

            def synth():  # HWC with the net's OWN channel count
                return rng.rand(h, w, c).astype(np.float32)
        else:
            def synth():  # non-image input: one row, preprocess reshapes
                return rng.rand(*shape[1:]).astype(np.float32)
        warmed = engine.compile_count
        # the HTTP leg decodes uploads with PIL convert("RGB"), so it
        # only makes sense for 3-channel image nets; others smoke the
        # engine surface alone
        n_http = min(4, args.smoke) \
            if len(shape) == 4 and shape[1] == 3 else 0
        http_err = None
        sent_http = 0
        try:
            from PIL import Image
            import io as _io
            for _ in range(n_http):
                buf = _io.BytesIO()
                Image.fromarray(rng.randint(0, 255, (h, w, 3), np.uint8)
                                ).save(buf, format="PNG")
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.server_address[1]}/classify",
                    data=buf.getvalue(),
                    headers={"Content-Type": "image/png"})
                json.loads(urllib.request.urlopen(req, timeout=60).read())
                sent_http += 1
        except ImportError:
            log.warning("PIL missing; smoke skips the HTTP leg")
        except Exception as e:  # noqa: BLE001 — an HTTP-leg failure must
            # still print the telemetry JSON below before failing the smoke
            http_err = e
            log.error("serve smoke: HTTP leg failed: %s", e)
        # the rest straight into the engine, in mixed-size bursts; count
        # from requests actually SENT so a skipped/failed HTTP leg does
        # not shrink the trace the operator asked for
        left = args.smoke - sent_http
        while left > 0:
            burst = int(rng.randint(1, model.fwd.ladder[-1] + 1))
            burst = min(burst, left)
            engine.classify("default", [synth() for _ in range(burst)])
            left -= burst
        engine.drain()
        stats = engine.stats()
        stats["post_warmup_compiles"] = engine.compile_count - warmed
        # decode-path engagement at a glance (ISSUE 14): the HTTP leg is
        # the request-ingest path — which decoder ran and whether the
        # window-fused preprocess engaged (full counters under "ingest")
        ing = stats["ingest"]
        stats["native_ingest_engaged"] = bool(
            ing["decode_plane"]["native_records"] > 0
            and ing["fused_rows"] > 0)
        print(json.dumps({"serve_smoke": stats}))
        if http_err is not None:
            return 1
        if args.require_native_ingest and (
                sent_http == 0 or not stats["native_ingest_engaged"]):
            log.error(
                "serve smoke: native ingest did NOT engage (http leg "
                "%d reqs, native decodes %d, fused rows %d) — build "
                "the native plane with caffe_mpi_tpu/native/build.sh",
                sent_http, ing["decode_plane"]["native_records"],
                ing["fused_rows"])
            return 1
        if args.require_bank_warm and (
                engine.bank is None or engine.compile_count != 0
                or engine.bank_hits != engine.warmed_buckets):
            log.error(
                "serve smoke: program bank was NOT warm (%d compiles, "
                "%d bank hits vs %d warmed buckets, bank %s) — the "
                "zero-compile cold-start claim did not hold",
                engine.compile_count, engine.bank_hits,
                engine.warmed_buckets,
                engine.bank.path if engine.bank else "OFF")
            return 1
        # zero-recompile invariant, extended for the program bank
        # (ISSUE 17): every warmed bucket either compiled (a counted
        # bank miss) or deserialized (a hit) — bank off, hits are 0 and
        # this is the classic compile_count == warmed_buckets
        if stats["post_warmup_compiles"] != 0 or \
                engine.compile_count != engine.bank_misses or \
                engine.compile_count + engine.bank_hits \
                != engine.warmed_buckets:
            log.error("serve smoke: steady-state serving COMPILED "
                      "(%d post-warmup; total %d vs %d warmed buckets, "
                      "bank hits %d misses %d)",
                      stats["post_warmup_compiles"], engine.compile_count,
                      engine.warmed_buckets, engine.bank_hits,
                      engine.bank_misses)
            return 1
        return 0
    finally:
        srv.shutdown()
        engine.close()


def cmd_device_query(args) -> int:
    import jax
    for d in jax.devices():
        print(f"device {d.id}: {d.device_kind} platform={d.platform} "
              f"process={d.process_index}")
        mem = getattr(d, "memory_stats", lambda: None)()
        if mem:
            print(f"  hbm: {mem.get('bytes_limit', 0) / 2**30:.1f} GiB limit, "
                  f"{mem.get('bytes_in_use', 0) / 2**20:.1f} MiB in use")
    return 0


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(levelname).1s%(asctime)s %(name)s] %(message)s",
        datefmt="%m%d %H:%M:%S")
    parser = _parser()
    args = parser.parse_args(argv)
    if args.profile and args.command not in ("train", "time"):
        parser.error(f"-profile is read by train and time, not by "
                     f"{args.command}")
    # the supervisor rebuilds the child command from the ORIGINAL argv
    # (argparse normalization would drop flag spellings)
    args._argv = list(argv) if argv is not None else sys.argv[1:]
    return {
        "train": cmd_train,
        "test": cmd_test,
        "time": cmd_time,
        "device_query": cmd_device_query,
        "serve": cmd_serve,
    }[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
