#!/usr/bin/env python
"""Benchmark — prints ONE JSON line with the headline metric.

Metric: AlexNet training throughput (img/s) at batch 256 on one chip —
f32 parameter storage and accumulation, MXU multiplies at XLA default
precision (the TPU analogue of NVCaffe's tensor-op math override; forcing
full-f32 multiplies via `default_forward_math: FLOAT` measures ~half).
Baseline: the reference's only published absolute throughput — CaffeNet,
20 iterations x 256 images in 19.2 s with cuDNN on a Tesla K40
(docs/performance_hardware.md:17-24) = 266.7 img/s; the 16-GPU results are
speedups over this class of single-GPU run (BASELINE.md).
vs_baseline = ours / 266.7. Also reports MFU: analytic fwd+bwd model FLOPs
(caffe_mpi_tpu/utils/flops.py) over measured step time and chip peak.

The full training step — forward, backward, SGD+momentum update — runs as
one jit-compiled XLA program, the same path `caffe train` uses.

Processes: the parent never imports jax — a process that has touched
jax holds the chip, and the child that needs it would fail or hang. It
runs ONE device child with a deadline, then the two CPU-side telemetry
children (serving, ingest; each block names the platform it ran on).
The device child refuses any platform but `tpu`. No TPU, a failed
child, or a failed phase is a non-zero exit: the line still prints,
with `value: null` and the error, but a failure is never reported as
success.
"""

import json
import math
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)

BASELINE_IMG_S = 256 * 20 / 19.2  # K40 + cuDNN, reference docs
DEVICE_DEADLINE_S = 600     # the device child: two compiles (f32 + the
                            # ISSUE 9 bf16 variant) + 2 x 23 steps
_IS_CHILD = os.environ.get("CAFFE_TPU_BENCH_CHILD") == "1"

# debug/staged knobs (the headline metric is always AlexNet f32 batch 256,
# 20 iters, step_chunk 10; overriding any knob renames the metric so an
# alternate line can't be mistaken for it). Staged configs
# (docs/mfu_analysis.md): CAFFE_BENCH_DTYPE=bf16 switches to the
# fp16 prototxt variant (FLOAT16 -> bf16 storage, f32 master weights);
# CAFFE_BENCH_MODEL=resnet50 benches the north-star topology.
# CAFFE_BENCH_STEP_CHUNK: iterations fused into one lax.scan dispatch
# (solver step_chunk; 20 timed iters at K=10 = 2 host dispatches instead
# of 20). Set 1 for the classic per-iteration dispatch mode.
BATCH = int(os.environ.get("CAFFE_BENCH_BATCH", 256))
WARMUP = int(os.environ.get("CAFFE_BENCH_WARMUP", 3))
ITERS = int(os.environ.get("CAFFE_BENCH_ITERS", 20))
MODEL = os.environ.get("CAFFE_BENCH_MODEL", "alexnet")
DTYPE = os.environ.get("CAFFE_BENCH_DTYPE", "f32")
STEP_CHUNK = max(int(os.environ.get("CAFFE_BENCH_STEP_CHUNK", 10)), 1)
# fused-eval telemetry phase (untimed; CAFFE_BENCH_EVAL=0 skips): 2 test
# boundaries overlapped with training, test_iter batches per pass fused
# at test_chunk batches per eval dispatch
EVAL_TEST_ITER = int(os.environ.get("CAFFE_BENCH_TEST_ITER", 8))
EVAL_TEST_CHUNK = int(os.environ.get("CAFFE_BENCH_TEST_CHUNK", 4))
# CAFFE_BENCH_GUARD: the on-device non-finite guard (ISSUE 4,
# solver train_guard). Default ON for the headline so the "guard is
# ~free on device" claim is what the committed number actually
# measures — the same program with per-step finiteness selects in the
# scan. skipped_steps / guard_syncs in the JSON are host-side counts
# (0 skips expected on synthetic data; guard_syncs = chunk
# boundaries, each a 5-scalar transfer). Set 0 for the unguarded
# program (renames the metric like every other knob).
GUARD = os.environ.get("CAFFE_BENCH_GUARD", "1") != "0"
# CAFFE_BENCH_MESH=all: run the headline config data-parallel over every
# visible device with the overlapped bucketed reduction engaged (ISSUE 6,
# solver reduce_overlap — parallel/reduction.py). The JSON line then
# carries a "reduction" block: collectives_per_step + bucket_bytes from
# the active plan and the HLO overlap-span proxy
# (reduction.collective_stats over the compiled step). Default "" keeps
# the 1-chip headline program unchanged; setting it renames the metric
# like every other knob.
MESH = os.environ.get("CAFFE_BENCH_MESH", "")
# CAFFE_BENCH_BF16: the mixed-precision headline variant (ISSUE 9,
# solver `precision` knob — docs/benchmarks.md "Mixed-precision bf16
# training"). Default ON: after the f32 headline region is banked
# (bitwise-untouched — the bf16 phase builds its OWN solver from a
# fresh parse of the same recipe), the child re-runs the same
# model/batch/step_chunk with `precision: bf16` + dynamic loss scaling
# and attaches a "bf16" block: img/s, MFU, speedup vs the f32 number,
# loss-scale/overflow counters, and (under CAFFE_BENCH_MESH=all) its
# own "reduction" block whose bucket_bytes are HALF the f32 ones (bf16
# wire). Set 0 to skip the phase; the headline metric is unaffected
# either way.
BF16 = os.environ.get("CAFFE_BENCH_BF16", "1") != "0"
# CAFFE_BENCH_SERVING: the inference-serving telemetry block (ISSUE 7,
# caffe_mpi_tpu/serving/ — docs/serving.md). Default ON: the parent
# runs tools/bench_serving.py in its own subprocess (CPU-forced inside
# that script; the block's `platform` key says so — its latencies are
# CPU numbers, not device metrics) and attaches its JSON — the
# zero-recompile proof (compile_count == warmed buckets across a
# mixed-size trace on two resident models) — to the emitted line. The
# headline metric itself is untouched (separate process, untimed).
SERVING = os.environ.get("CAFFE_BENCH_SERVING", "1") != "0"
SERVING_DEADLINE_S = 180
# CAFFE_BENCH_INGEST: the host-ingestion telemetry block (ISSUE 10,
# native/decode.cc + data/decode.py — docs/benchmarks.md "Ingestion").
# Default ON: the parent runs `bench_data --ingest-only --json` in its
# own subprocess (host CPU only, no jax import; the block is tagged
# `platform: cpu`) and attaches the `ingest` JSON — per-stage ms/batch
# (read/crc/decode/transform/assemble over a JPEG-encoded LMDB), the
# PIL-vs-native-fused img/s A/B, and the decoded-cache epoch-2 rate —
# to the emitted line. The headline metric itself is untouched
# (separate process, untimed).
INGEST = os.environ.get("CAFFE_BENCH_INGEST", "1") != "0"
INGEST_DEADLINE_S = 240
_SOLVERS = {
    ("alexnet", "f32"): "models/alexnet/solver.prototxt",
    ("alexnet", "bf16"): "models/alexnet/solver_fp16.prototxt",
    ("resnet50", "f32"): "models/resnet50/solver.prototxt",
    ("resnet50", "bf16"): "models/resnet50/solver_fp16.prototxt",
}
# BF16 deliberately absent from the debug-rename tuple: the bf16 phase
# runs after the f32 region and cannot perturb the headline number
_IS_DEBUG = (BATCH, ITERS, WARMUP, MODEL, DTYPE, STEP_CHUNK,
             EVAL_TEST_ITER, EVAL_TEST_CHUNK, GUARD, MESH) != (
                 256, 20, 3, "alexnet", "f32", 10, 8, 4, True, "")
METRIC = ("alexnet_b256_train_img_per_s_1chip" if not _IS_DEBUG
          else f"debug_{MODEL}_{DTYPE}_b{BATCH}_i{ITERS}_k{STEP_CHUNK}"
               f"{'' if GUARD else '_noguard'}"
               f"{f'_mesh_{MESH}' if MESH else ''}_train_img_per_s_1chip")


def emit(value=None, vs_baseline=None, extra=None, error=None):
    line = {"metric": METRIC, "value": value, "unit": "img/s",
            "vs_baseline": vs_baseline}
    if extra:
        line.update(extra)
    if error:
        line["error"] = error
    print(json.dumps(line))
    sys.stdout.flush()


def run_bench():
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; jax found platform "
            f"{device.platform!r} ({device.device_kind}). A CPU number is "
            f"never written under a device metric's name.")

    # warm-cacheable compiles: later runs skip the AlexNet-step compile
    from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver
    from caffe_mpi_tpu.utils.flops import peak_flops, train_flops_per_image

    try:
        solver_path = _SOLVERS[(MODEL, DTYPE)]
    except KeyError:
        raise SystemExit(f"unknown bench config model={MODEL} dtype={DTYPE}; "
                         f"known: {sorted(_SOLVERS)}")
    sp = SolverParameter.from_file(os.path.join(_ROOT, solver_path))
    sp.max_iter = 10**9
    sp.display = 0
    sp.snapshot = 0
    sp.test_interval = 0
    sp.step_chunk = STEP_CHUNK
    sp.train_guard = GUARD
    from caffe_mpi_tpu.utils.model_shapes import input_shapes, synthetic_feeds
    npar = NetParameter.from_file(os.path.join(_ROOT, sp.net))
    shapes = input_shapes(npar, batch=BATCH)
    sp.net = ""
    sp.net_param = npar
    mesh_plan = None
    if MESH:
        if MESH != "all":
            raise SystemExit(f"unknown CAFFE_BENCH_MESH={MESH!r}; "
                             "supported: 'all'")
        from caffe_mpi_tpu.parallel import MeshPlan, reduction
        mesh_plan = MeshPlan.data_parallel()
        sp.reduce_overlap = True
        # same libtpu scheduler flags `caffe train -reduce_overlap`
        # sets (no-op on CPU; nothing above has touched the device, so
        # this lands before backend init) — the bench must measure the
        # bucketed program WITH the latency-hiding scheduler, not the
        # collectives serialized
        reduction.apply_tpu_overlap_flags(os.environ)
    solver = Solver(sp, model_dir=_ROOT, mesh=mesh_plan)

    feeds = synthetic_feeds(shapes, npar=npar)
    feed_fn = lambda it: feeds

    # warmup (compile + first steps). With K-step fusion active, warm at
    # least one FULL chunk so the timed region reuses the compiled scan
    # program instead of compiling it on the clock.
    warmup = max(WARMUP, sp.step_chunk if sp.step_chunk > 1 else 0)
    solver.step(warmup, feed_fn)
    jax.block_until_ready(solver.params)

    d0, s0 = solver.dispatch_count, solver.host_sync_count
    g0 = solver.guard_sync_count
    t0 = time.perf_counter()
    solver.step(ITERS, feed_fn)
    jax.block_until_ready(solver.params)
    dt = time.perf_counter() - t0
    dispatches = solver.dispatch_count - d0
    host_syncs = solver.host_sync_count - s0
    guard_syncs = solver.guard_sync_count - g0

    img_s = BATCH * ITERS / dt
    flops_img = train_flops_per_image(solver.net)
    achieved = flops_img * img_s

    # fused-eval telemetry (ISSUE 2), measured OUTSIDE the timed region:
    # drive test boundaries overlapped with training and report the
    # dispatch accounting — test_dispatches_per_pass should be
    # ceil(test_iter/T) + 1 (the +1 is the shared-param copy), and
    # eval_stall_ms is the host time the TRAIN loop lost per pass
    # (boundary dispatch + harvest wait), NOT the full pass. Counted
    # host-side like dispatches_per_100_iters. The headline img/s above
    # is untouched (its region ran with test_interval 0).
    eval_extra = {}
    if solver.test_nets and os.environ.get("CAFFE_BENCH_EVAL", "1") != "0":
        sp.test_iter = [EVAL_TEST_ITER]
        sp.test_interval = 3
        sp.test_chunk = EVAL_TEST_CHUNK
        tfeed = [lambda k: feeds]
        # warmup: compile the eval scan + param-copy programs OFF the
        # stall clock (same reason the train region warms a full chunk)
        solver.test_all(tfeed)
        d0, p0, s0 = (solver.test_dispatch_count, solver.test_pass_count,
                      solver.eval_stall_ms)
        solver.step(6, feed_fn, test_feed_fns=tfeed)
        jax.block_until_ready(solver.params)
        passes = solver.test_pass_count - p0
        if passes:
            eval_extra = {
                "test_iter": EVAL_TEST_ITER,
                "test_chunk": EVAL_TEST_CHUNK,
                "test_dispatches_per_pass": round(
                    (solver.test_dispatch_count - d0) / passes, 1),
                "eval_stall_ms": round(
                    (solver.eval_stall_ms - s0) / passes, 1),
            }

    peak = peak_flops(device)  # raises for a TPU kind not in the table
    extra = {
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "model_tflops_per_s": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        # host dispatches per 100 training iterations: ~100 in classic
        # mode, ~100/K + host-event syncs with K-step fusion (a count,
        # not a rate)
        "step_chunk": sp.step_chunk,
        "dispatches_per_100_iters": round(dispatches * 100 / ITERS, 1),
        # 0 in the headline config (display off): the timed region never
        # blocks on the device between chunks
        "host_syncs": host_syncs,
        # self-healing guard telemetry (ISSUE 4): skipped_steps must be
        # 0 on synthetic data (any other value is itself a finding);
        # guard_syncs counts the per-chunk 5-scalar counter reads — the
        # guard's ONLY host traffic, so "~free on device" is measured
        # by comparing this line against CAFFE_BENCH_GUARD=0
        "train_guard": sp.train_guard,
        "skipped_steps": solver.skipped_steps,
        "guard_syncs": guard_syncs,
    }
    extra.update(eval_extra)
    if mesh_plan is not None:
        # ISSUE 6 telemetry, computed OUTSIDE the timed region: the
        # active bucket plan (collectives_per_step, bucket_bytes — or
        # mode "implicit" + fallback_reason when the net couldn't
        # engage) plus the HLO overlap-span proxy from a one-iteration
        # compile (reduction.collective_stats; one extra XLA compile,
        # after the headline number is already banked)
        rstats = solver.reduction_stats() or {}
        rstats.update(reduction.collective_stats(
            solver.step_hlo_text(feeds)))
        extra["reduction"] = rstats

    # ISSUE 9: the bf16 headline variant, measured AFTER the f32 number
    # is banked. A fresh parse of the same recipe + `precision: bf16`
    # (dynamic loss scaling by default) — same model, batch, step_chunk,
    # guard — so the pair of numbers is the one-knob A/B the precision
    # section of docs/benchmarks.md quotes. The f32 metric above is
    # bitwise-untouched: nothing here runs before it.
    if BF16 and DTYPE == "f32":
        sp2 = SolverParameter.from_file(os.path.join(_ROOT, solver_path))
        sp2.max_iter = 10**9
        sp2.display = 0
        sp2.snapshot = 0
        sp2.test_interval = 0
        sp2.step_chunk = STEP_CHUNK
        sp2.train_guard = GUARD
        sp2.precision = "bf16"
        if mesh_plan is not None:
            sp2.reduce_overlap = True  # fresh parse: re-opt-in
        sp2.net = ""
        sp2.net_param = npar
        solver2 = Solver(sp2, model_dir=_ROOT, mesh=mesh_plan)
        warm2 = max(WARMUP, STEP_CHUNK if STEP_CHUNK > 1 else 0)
        solver2.step(warm2, feed_fn)
        jax.block_until_ready(solver2.params)
        t0 = time.perf_counter()
        solver2.step(ITERS, feed_fn)
        jax.block_until_ready(solver2.params)
        dt2 = time.perf_counter() - t0
        img_s2 = BATCH * ITERS / dt2
        bf16 = {
            "img_per_s": round(img_s2, 1),
            "mfu": round(flops_img * img_s2 / peak, 4),
            "speedup_vs_f32": round(img_s2 / img_s, 2),
            # dynamic loss-scale telemetry: 0 overflows expected on
            # synthetic data, scale at its 2^15 start
            "loss_scale": solver2.loss_scale_value,
            "overflow_steps": solver2.overflow_steps,
            "skipped_steps": solver2.skipped_steps,
        }
        if mesh_plan is not None:
            # bucket_bytes here are HALF the f32 reduction block's:
            # the buckets pack and psum in bf16 (wire_dtype)
            bf16["reduction"] = solver2.reduction_stats() or {}
        solver2.close()
        extra["bf16"] = bf16
    return round(img_s, 1), round(img_s / BASELINE_IMG_S, 2), extra


def serving_block():
    """Run the serving bench in a child; returns the `serving` dict (or
    {"error": ...}). The child forces the CPU platform and says so in
    the block's `platform` key."""
    script = os.path.join(_ROOT, "tools", "bench_serving.py")
    try:
        r = subprocess.run([sys.executable, script], text=True,
                           capture_output=True, timeout=SERVING_DEADLINE_S)
    except subprocess.TimeoutExpired:
        return {"error": f"serving bench exceeded {SERVING_DEADLINE_S}s"}
    for line in reversed(r.stdout.strip().splitlines() or [""]):
        if line.startswith("{"):
            try:
                block = json.loads(line)["serving"]
            except (ValueError, KeyError):
                break
            if r.returncode != 0:
                block["error"] = "zero-recompile assertion FAILED"
            return block
    tail = [l for l in r.stderr.strip().splitlines() if l.strip()]
    return {"error": (tail[-1][-300:] if tail
                      else f"serving bench exited rc={r.returncode}")}


def ingest_block():
    """Run the ingestion bench in a child; returns the `ingest` dict
    (or {"error": ...}). Host CPU work only (no jax import)."""
    cmd = [sys.executable, "-m", "caffe_mpi_tpu.tools.bench_data",
           "--ingest-only", "--json", "--ingest-n", "768",
           "-batch", "128"]
    try:
        r = subprocess.run(cmd, text=True, capture_output=True, cwd=_ROOT,
                           timeout=INGEST_DEADLINE_S)
    except subprocess.TimeoutExpired:
        return {"error": f"ingest bench exceeded {INGEST_DEADLINE_S}s"}
    for line in reversed(r.stdout.strip().splitlines() or [""]):
        if line.startswith("{"):
            try:
                block = json.loads(line)["ingest"]
            except (ValueError, KeyError):
                break
            block["platform"] = "cpu"
            return block
    tail = [l for l in r.stderr.strip().splitlines() if l.strip()]
    return {"error": (tail[-1][-300:] if tail
                      else f"ingest bench exited rc={r.returncode}")}


def device_child():
    """Run the bench body in ONE child with a deadline; return
    (json_line|None, err)."""
    env = dict(os.environ, CAFFE_TPU_BENCH_CHILD="1")
    try:
        r = subprocess.run([sys.executable, __file__], env=env, text=True,
                           capture_output=True, timeout=DEVICE_DEADLINE_S)
    except subprocess.TimeoutExpired:
        return None, (f"device child exceeded its {DEVICE_DEADLINE_S}s "
                      "deadline")
    sys.stderr.write(r.stderr)
    if r.returncode == 0 and r.stdout.strip():
        return r.stdout.strip().splitlines()[-1], None
    tail = [l for l in r.stderr.strip().splitlines() if l.strip()]
    return None, (tail[-1][-300:] if tail
                  else f"device child exited rc={r.returncode}")


if __name__ == "__main__":
    if _IS_CHILD:
        # child: device work only; crash loudly on failure (parent reports)
        value, vs, extra = run_bench()
        emit(value, vs, extra)
        sys.exit(0)

    # device first: with no TPU there is nothing to attach telemetry to
    line, err = device_child()
    if line is None:
        emit(error=err)
        sys.exit(1)
    telemetry = {}
    if SERVING:
        telemetry["serving"] = serving_block()
    if INGEST:
        telemetry["ingest"] = ingest_block()
    obj = json.loads(line)
    obj.update(telemetry)
    print(json.dumps(obj))
    failed = [k for k, block in telemetry.items() if "error" in block]
    if failed:
        print(f"bench: CPU-side block(s) failed: {failed}", file=sys.stderr)
        sys.exit(1)
    sys.exit(0)
