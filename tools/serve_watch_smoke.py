#!/usr/bin/env python
"""Verified hot-swap smoke (ISSUE 12) — prints ONE JSON line.

The train->serve loop end to end, against whatever device jax finds
(not yet run on the chip): a ServingEngine serves live
traffic while a SnapshotWatcher tails a snapshot prefix; the smoke
publishes (1) a verified 3x-scaled snapshot that MUST swap in with
zero recompiles and visibly changed scores, then (2) a corrupt
snapshot (one flipped byte post-manifest) that MUST be rejected with
the swapped weights still serving bitwise-identical scores.

Usage: python tools/serve_watch_smoke.py [--json]
Exit 0 iff every claim held.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

DEPLOY = """
name: "watch_toy"
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


def publish(prefix, it, net, resilience):
    mpath = f"{prefix}_iter_{it}.caffemodel"
    net.save(mpath)
    spath = f"{prefix}_iter_{it}.solverstate"
    with open(spath, "wb") as f:  # the watcher only consumes the model
        f.write(b"state-stub")
    resilience.write_snapshot_manifest(spath, it,
                                       {"model": mpath, "state": spath})
    return mpath


def main() -> int:
    import numpy as np
    import caffe_mpi_tpu.pycaffe as caffe
    from caffe_mpi_tpu.serving import ServingEngine, SnapshotWatcher
    from caffe_mpi_tpu.utils import resilience

    tmp = tempfile.mkdtemp(prefix="caffe_serve_watch_")
    model = os.path.join(tmp, "deploy.prototxt")
    with open(model, "w") as f:
        f.write(DEPLOY)
    net = caffe.Net(model, caffe.TEST)
    w1 = os.path.join(tmp, "w1.caffemodel")
    net.save(w1)
    prefix = os.path.join(tmp, "snap")

    rng = np.random.RandomState(0)
    probe = [rng.rand(12, 12, 3).astype(np.float32) for _ in range(4)]
    eng = ServingEngine(window_ms=2, journal=os.path.splitext(model)[0])
    eng.load_model("default", model, w1)
    warmed = eng.compile_count
    watcher = SnapshotWatcher(eng, "default", prefix, poll_s=0.1)
    watcher.start()
    t0 = time.perf_counter()

    base = eng.classify("default", probe)

    # 1) verified snapshot -> must swap, visibly, with zero compiles
    net.params["ip"][0].data = net.params["ip"][0].data * 3.0
    publish(prefix, 10, net, resilience)
    deadline = time.time() + 60
    while eng.swaps == 0 and time.time() < deadline:
        time.sleep(0.05)
    swapped = eng.classify("default", probe)

    # 2) corrupt snapshot (post-manifest bitrot) -> must be rejected
    net.params["ip"][0].data = net.params["ip"][0].data * 5.0
    bad = publish(prefix, 20, net, resilience)
    with open(bad, "r+b") as f:
        f.seek(os.path.getsize(bad) // 2)
        byte = f.read(1)
        f.seek(os.path.getsize(bad) // 2)
        f.write(bytes([byte[0] ^ 0xFF]))
    deadline = time.time() + 60
    while eng.swap_rejections == 0 and time.time() < deadline:
        time.sleep(0.05)
    after_reject = eng.classify("default", probe)

    watcher.stop()
    stats = eng.stats()
    eng.shutdown()

    import jax
    out = {
        "platform": jax.devices()[0].platform,
        "elapsed_s": round(time.perf_counter() - t0, 1),
        "swaps": stats["swaps"],
        "swap_rejections": stats["swap_rejections"],
        "swap_changed_scores": bool(not np.allclose(base, swapped)),
        "reject_kept_scores_bitwise": bool(
            np.array_equal(swapped, after_reject)),
        "post_warmup_compiles": stats["compile_count"] - warmed,
        "zero_recompile": stats["compile_count"] == stats["warmed_buckets"],
        "p99_ms": stats.get("p99_ms"),
    }
    out["ok"] = (out["swaps"] == 1 and out["swap_rejections"] == 1
                 and out["swap_changed_scores"]
                 and out["reject_kept_scores_bitwise"]
                 and out["post_warmup_compiles"] == 0
                 and out["zero_recompile"])
    print(json.dumps({"serve_watch": out}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
