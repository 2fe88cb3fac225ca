#!/usr/bin/env python
"""Model-zoo throughput sweep on the real TPU (docs/benchmarks.md source).

For each (model, batch) the full training step — forward, backward,
optimizer update — runs as one jit-compiled XLA program on synthetic
on-device data (pipeline excluded; `bench_data` measures that side), the
same path `caffe train` uses. Reports img/s and model-FLOPs MFU.

One process per chip: the parent never imports jax; every model runs in
its own child (own process group, hard deadline, killed on every exit
path), so one hang cannot stall the sweep or leave a child holding the
chip. The child refuses any platform but `tpu`, and the sweep exits
non-zero when any model failed, timed out, or found no TPU.

Usage:
    python tools/bench_models.py [model ...]   # default: the zoo ladder
    python tools/bench_models.py resnet50 resnet50_fp16

Reference anchors (BASELINE.md): CaffeNet 256x20 imgs in 19.2 s on K40
(266.7 img/s); 16xP40 cluster speedups 14.65x/14.25x/15.34x for
AlexNet/GoogLeNet/ResNet over one P40.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from caffe_mpi_tpu.utils.subproc import run_contained  # noqa: E402

# model key -> (solver path, batch override or None=prototxt, note)
SWEEP = {
    "alexnet": ("models/alexnet/solver.prototxt", 256, "headline topology"),
    "googlenet": ("models/googlenet/solver.prototxt", 128,
                  "reference 16-P40 run used global batch 128"),
    "resnet50": ("models/resnet50/solver.prototxt", 32,
                 "reference per-GPU batch"),
    "resnet50_b256": ("models/resnet50/solver.prototxt", 256,
                      "DGX-1-recipe batch"),
    "resnet50_fp16": ("models/resnet50/solver_fp16.prototxt", 32,
                      "bf16 compute policy (FLOAT16->bf16 mapping)"),
    "resnet50_b256_fp16": ("models/resnet50/solver_fp16.prototxt", 256,
                           "north-star config: DGX batch + bf16 storage "
                           "(docs/mfu_analysis.md)"),
    "alexnet_fp16": ("models/alexnet/solver_fp16.prototxt", 256,
                     "headline topology, bf16 storage"),
    "vgg16": ("models/vgg16/solver.prototxt", 32, None),
    "inception_v3": ("models/inception_v3/solver.prototxt", 32, None),
    "cifar10_quick": ("models/cifar10_quick/solver.prototxt", 100, None),
}
DEFAULT = ["alexnet", "alexnet_fp16", "googlenet", "resnet50",
           "resnet50_b256", "resnet50_fp16", "resnet50_b256_fp16",
           "vgg16", "inception_v3"]
_CHILD = os.environ.get("CAFFE_BENCH_MODELS_CHILD")


def bench_one(key: str) -> dict:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"bench_models measures the TPU; jax found "
                         f"platform {device.platform!r}")

    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver
    from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache
    from caffe_mpi_tpu.utils.flops import peak_flops, train_flops_per_image

    enable_compile_cache()
    solver_path, batch, _note = SWEEP[key]
    sp = SolverParameter.from_file(os.path.join(_ROOT, solver_path))
    sp.max_iter = 10**9
    sp.display = 0
    sp.snapshot = 0
    sp.test_interval = 0
    from caffe_mpi_tpu.utils.model_shapes import input_shapes, synthetic_feeds
    npar = NetParameter.from_file(os.path.join(_ROOT, sp.net))
    shapes = input_shapes(npar, batch=batch)
    sp.net = ""
    sp.net_param = npar
    solver = Solver(sp, model_dir=_ROOT)

    # class count = num_output of the layer feeding the loss (labels drawn
    # beyond it would silently clamp in take_along_axis and skew the loss)
    loss_bottoms = [l.bottom[0] for l in npar.layer
                    if "Loss" in l.type and l.bottom]
    n_classes = 1000
    for l in npar.layer:
        if l.type == "InnerProduct" and l.top and \
                l.top[0] in loss_bottoms and l.inner_product_param.num_output:
            n_classes = l.inner_product_param.num_output
    feeds = synthetic_feeds(shapes, n_classes=n_classes, npar=npar)
    feed_fn = lambda it: feeds

    iters, warmup = 20, 3
    solver.step(warmup, feed_fn)
    jax.block_until_ready(solver.params)
    t0 = time.perf_counter()
    solver.step(iters, feed_fn)
    jax.block_until_ready(solver.params)
    dt = time.perf_counter() - t0

    n = next(iter(shapes.values()))[0]
    img_s = n * iters / dt
    flops_img = train_flops_per_image(solver.net)
    peak = peak_flops(device)  # raises for a TPU kind not in the table
    achieved = flops_img * img_s
    return {
        "model": key, "batch": n, "img_per_s": round(img_s, 1),
        "step_ms": round(dt / iters * 1e3, 2),
        "tflops_per_s": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        "device": device.device_kind,
    }


def main() -> int:
    if _CHILD:
        print(json.dumps(bench_one(_CHILD)))
        return 0
    if any(a in ("-h", "--help") for a in sys.argv[1:]):
        print(__doc__)
        print(f"known model keys: {sorted(SWEEP)}")
        return 0
    keys = sys.argv[1:] or DEFAULT
    bad = [k for k in keys if k not in SWEEP]
    if bad:
        print(f"unknown model keys: {bad}; known: {sorted(SWEEP)}")
        return 2
    results = []
    failed = []
    for key in keys:
        env = dict(os.environ, CAFFE_BENCH_MODELS_CHILD=key)
        # generous deadline: first-run compile of the big nets is slow
        rc, out, err = run_contained([sys.executable, __file__], 900,
                                     cwd=_ROOT, env=env)
        if rc is None:
            failed.append(key)
            print(f"{key:>14}: TIMEOUT (900s)", flush=True)
        elif rc == 0 and out.strip():
            rec = json.loads(out.strip().splitlines()[-1])
            results.append(rec)
            print(f"{key:>14}: {rec['img_per_s']:8.1f} img/s  "
                  f"b{rec['batch']}  {rec['step_ms']:7.2f} ms/step  "
                  f"MFU {rec['mfu']:.1%}", flush=True)
        else:
            failed.append(key)
            tail = err.strip().splitlines()[-1:] or ["(no output)"]
            print(f"{key:>14}: FAILED rc={rc} {tail[0][-200:]}", flush=True)
    if results:
        with open(os.path.join(_ROOT, "bench_models.json"), "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote bench_models.json ({len(results)} entries)")
    if failed:
        print(f"FAILED: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
