#!/usr/bin/env python
"""Serving-plane benchmark — prints ONE JSON line (bench.py `serving`).

Reference: the reference framework publishes no serving numbers (its
deployment story, examples/web_demo + extract_features, is unmetered);
this is the measurement ISSUE 7's acceptance demands: a mixed-size
synthetic arrival trace across TWO resident models under an HBM budget
must run **zero post-warmup compiles** (compile_count == warmed bucket
count) while reporting p50/p99 end-to-end latency and sustained img/s.

Runs CPU-forced by default — its fleet phase starts several engine
processes, and a chip belongs to one process at a time — and says so:
the emitted block carries `platform`, and its latencies and img/s are
CPU numbers, not device metrics. CAFFE_BENCH_SERVING_DEVICE=1 lets jax
pick the platform (not yet run on the chip; chip_smoke.py's serve leg
covers the hardware HTTP path via `caffe serve -smoke`).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

if os.environ.get("CAFFE_BENCH_SERVING_DEVICE") != "1":
    # must land before any jax computation (backends init lazily)
    import jax
    jax.config.update("jax_platforms", "cpu")

CONV_NET = """
name: "serve_conv"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 16 dim: 3 dim: 16 dim: 16 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 8 kernel_size: 3 stride: 2
          weight_filler { type: "xavier" } } }
layer { name: "ip" type: "InnerProduct" bottom: "c" top: "score"
        inner_product_param { num_output: 10
          weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "score" top: "prob" }
"""

MLP_NET = """
name: "serve_mlp"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: 8 dim: 1 dim: 8 dim: 8 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "h"
        inner_product_param { num_output: 32
          weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "h" top: "h" }
layer { name: "ip2" type: "InnerProduct" bottom: "h" top: "score"
        inner_product_param { num_output: 5
          weight_filler { type: "xavier" } } }
layer { name: "prob" type: "Softmax" bottom: "score" top: "prob" }
"""

REQUESTS = int(os.environ.get("CAFFE_BENCH_SERVING_REQS", 200))
WINDOW_MS = float(os.environ.get("CAFFE_BENCH_SERVING_WINDOW_MS", 2.0))


def main() -> int:
    import numpy as np
    from caffe_mpi_tpu.serving import ServingEngine

    tmp = tempfile.mkdtemp(prefix="caffe_serve_bench_")
    nets = {"conv": CONV_NET, "mlp": MLP_NET}
    paths = {}
    for name, text in nets.items():
        paths[name] = os.path.join(tmp, f"{name}.prototxt")
        with open(paths[name], "w") as f:
            f.write(text)

    # phase 1, unlimited HBM: both models resident — this trace measures
    # steady-state latency, which residency thrash would pollute; the
    # budgeted LRU path gets its own phase below
    eng = ServingEngine(window_ms=WINDOW_MS)
    t_load0 = time.perf_counter()
    for name in nets:
        eng.load_model(name, paths[name])
    load_ms = (time.perf_counter() - t_load0) * 1e3
    warmed = eng.warmed_buckets
    compiles_at_warm = eng.compile_count

    # mixed-size arrival trace: bursts of 1..max interleaved across the
    # two models, drained fully before reading stats
    rng = np.random.RandomState(0)
    shapes = {"conv": (16, 16, 3), "mlp": (8, 8, 1)}
    sent = 0
    futures = []
    while sent < REQUESTS:
        name = "conv" if rng.rand() < 0.5 else "mlp"
        maxb = eng.model(name).fwd.ladder[-1]
        burst = int(rng.randint(1, maxb + 1))
        for _ in range(min(burst, REQUESTS - sent)):
            h, w, c = shapes[name]
            img = rng.rand(h, w, c).astype(np.float32)
            futures.append(eng.submit(name, img))
            sent += 1
    eng.drain(timeout=120)
    for f in futures:
        f.result(timeout=1)  # surfaces any dispatch failure loudly

    stats = eng.stats()
    stats["load_ms"] = round(load_ms, 1)
    stats["requests_sent"] = sent
    stats["post_warmup_compiles"] = eng.compile_count - compiles_at_warm
    stats["zero_recompile"] = (stats["post_warmup_compiles"] == 0
                               and eng.compile_count == warmed)

    # budgeted phase: a SECOND engine under a deliberately tight HBM
    # budget (one model fits, both do not) proves the LRU path live —
    # alternating traffic spills and reloads, and reloads are pure
    # device_puts, never recompiles. Kept separate so residency thrash
    # cannot pollute the steady-state latency numbers above.
    sizes = [eng.model(n).param_bytes for n in nets]
    budget_mb = (max(sizes) + min(sizes) / 2) / 2**20
    eng.close()
    eng2 = ServingEngine(window_ms=0, hbm_mb=budget_mb)
    for name in nets:
        eng2.load_model(name, paths[name])
    warmed2 = eng2.warmed_buckets
    compiles2 = eng2.compile_count
    for i in range(6):  # alternate models -> every round spills one
        name = ("conv", "mlp")[i % 2]
        h, w, c = shapes[name]
        eng2.classify(name, [rng.rand(h, w, c).astype(np.float32)])
    eng2.drain(timeout=60)
    stats["budgeted"] = {
        "hbm_mb": round(budget_mb, 3),
        "spills": eng2.spills,
        "reloads": eng2.reloads,
        "post_warmup_compiles": eng2.compile_count - compiles2,
        "zero_recompile": (eng2.compile_count == warmed2
                           and eng2.spills > 0 and eng2.reloads > 0),
    }
    eng2.close()

    # swap-under-traffic phase (ISSUE 12): live traffic ACROSS a
    # verified hot-swap — the watcher verifies the snapshot's crc32c
    # manifest, canary-gates the candidate on an already-compiled
    # bucket, and swaps weights WITHOUT touching the compiled ladder.
    # Enforced claims: zero post-warmup compiles and p99 within 1.5x
    # the phase's OWN pre-swap baseline (the identical paced trace run
    # twice — comparing against phase 1's unpaced flood would make the
    # bound vacuous).
    stats["swap"] = swap_phase(paths["conv"], shapes["conv"], tmp)

    # overload-shed phase (ISSUE 12): offered load > capacity against a
    # tight serve_queue_limit — typed sheds, backlog provably bounded
    stats["shed"] = shed_phase(paths["mlp"], shapes["mlp"])

    # native request ingest phase (ISSUE 14): PIL-vs-native A/B on the
    # same encoded request trace + cached replay — the serving half of
    # PR 9's ingest roofline, measured where it runs (the host)
    stats["ingest"] = ingest_phase(paths["conv"], tmp)

    # cold-start phase (ISSUE 17): bank-off vs bank-cold vs bank-warm
    # engine starts on the same two-model zoo — the bank-warm restart
    # must run ZERO compiles with bitwise score parity
    stats["cold_start"] = cold_start_phase(paths, shapes, tmp)

    # fleet phase (ISSUE 18): 2 real replica processes behind the
    # typed-retry router — replica kill under live traffic (p99 holds,
    # every future typed, bank-warm zero-compile respawn, journaled
    # replica_dead) plus the rolling canary swap + bitwise rejection
    stats["fleet"] = fleet_phase()

    import jax
    stats["platform"] = jax.devices()[0].platform
    print(json.dumps({"serving": stats}))
    ok = (stats["zero_recompile"]
          and stats["budgeted"]["zero_recompile"]
          and stats["swap"]["ok"] and stats["shed"]["ok"]
          and stats["ingest"]["ok"] and stats["cold_start"]["ok"]
          and stats["fleet"]["ok"])
    return 0 if ok else 1


def fleet_phase() -> dict:
    """Run tools/fleet_smoke.py in-process (the same import idiom as
    swap_phase's serve_watch_smoke publish helper) and fold its report
    into the serving line: shed/retry accounting, p99-under-kill,
    bank-warm respawn, rolling-swap + rejection bitwise-ness. The
    smoke's `ok` is rc-enforced here like every other phase."""
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    import fleet_smoke
    report = fleet_smoke.run_fleet_smoke()
    return {
        "ok": bool(report.get("ok")),
        "baseline_p99_ms": report.get("baseline", {}).get("p99_ms"),
        "kill": report.get("kill"),
        "respawn": report.get("respawn"),
        "swap": report.get("swap"),
        "reject": report.get("reject"),
        "elapsed_s": report.get("elapsed_s"),
    }


def swap_phase(model_path: str, shape, tmp: str) -> dict:
    import numpy as np
    import caffe_mpi_tpu.pycaffe as caffe
    from caffe_mpi_tpu.serving import ServingEngine, SnapshotWatcher
    from caffe_mpi_tpu.utils import resilience
    # the one spelling of "publish a verified snapshot set" shared with
    # the serve-watch smoke (tools/ is not a package; _ROOT is already
    # on sys.path for the caffe_mpi_tpu import above)
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    from serve_watch_smoke import publish

    net = caffe.Net(model_path, caffe.TEST)
    w1 = os.path.join(tmp, "swap_w1.caffemodel")
    net.save(w1)
    prefix = os.path.join(tmp, "swap_snap")

    eng = ServingEngine(window_ms=WINDOW_MS)
    eng.load_model("m", model_path, w1)
    warmed = eng.compile_count
    rng = np.random.RandomState(2)
    h, w, c = shape
    maxb = eng.model("m").fwd.ladder[-1]

    def paced_trace():
        """One paced mixed-size trace; returns its own p99 (records
        sliced to THIS trace so the two runs are comparable)."""
        seen = len(eng._batcher.records())
        futures = []
        sent = 0
        while sent < REQUESTS:
            burst = int(rng.randint(1, maxb + 1))
            for _ in range(min(burst, REQUESTS - sent)):
                futures.append(eng.submit(
                    "m", rng.rand(h, w, c).astype(np.float32)))
                sent += 1
            time.sleep(0.002)  # paced: the swap must land MID-traffic
        eng.drain(timeout=120)
        for f in futures:
            f.result(timeout=1)
        lat = [r["total_ms"] for r in eng._batcher.records()[seen:]]
        return sent, float(np.percentile(np.array(lat), 99))

    # the identical trace, first without the watcher (the baseline),
    # then with the watcher swapping MID-trace — apples to apples. The
    # during-trace requirement is enforced, not assumed: each attempt
    # publishes a fresh verified snapshot and only a trace whose
    # swap-counter advanced while it ran counts (a swap landing between
    # traces would silently compare two no-swap traces); a slow host
    # gets three attempts before the phase reports failure. The
    # baseline is the MAX of two runs of the same trace: at CPU-forced
    # ~5 ms p99s a single run's p99 jitters tens of percent on a
    # shared host, and a falsely tight baseline fails the ratio bound
    # without any swap regression.
    n_base1, p99_b1 = paced_trace()
    n_base2, p99_b2 = paced_trace()
    n_total, p99_base = n_base1 + n_base2, max(p99_b1, p99_b2)
    watcher = SnapshotWatcher(eng, "m", prefix, poll_s=0.05)
    watcher.start()
    p99_swap = None
    swap_during_trace = False
    for attempt in range(3):
        net.params["ip"][0].data = net.params["ip"][0].data * 3.0
        publish(prefix, 10 * (attempt + 1), net, resilience)
        s0 = eng.swaps
        n, p99 = paced_trace()
        n_total += n
        if eng.swaps > s0:
            p99_swap = p99
            swap_during_trace = True
            break
        # not yet: let the pending swap land, then retry with a new one
        deadline = time.time() + 10
        while eng.swaps == s0 and time.time() < deadline:
            time.sleep(0.01)
    watcher.stop()
    eng.close()
    ratio = (p99_swap / p99_base) if (p99_base and p99_swap) else None
    out = {
        "requests": n_total,
        "swaps": eng.swaps,
        "swap_rejections": eng.swap_rejections,
        "swap_during_trace": swap_during_trace,
        "p99_ms": round(p99_swap, 3) if p99_swap else None,
        "baseline_p99_ms": round(p99_base, 3),
        "p99_ratio_vs_baseline": round(ratio, 3) if ratio else None,
        "post_warmup_compiles": eng.compile_count - warmed,
        "zero_recompile_during_swap": (
            eng.compile_count == warmed
            and eng.compile_count == eng.warmed_buckets),
        # the enforced bound is the 1.5x ratio; the 5 ms absolute floor
        # only absorbs scheduler jitter on the CPU-forced run (p99 ~5
        # ms here) — at larger latencies the ratio term dominates and
        # the floor is inert
        "p99_held": (p99_swap is not None
                     and p99_swap <= max(1.5 * p99_base,
                                         p99_base + 5.0)),
    }
    out["ok"] = (eng.swaps >= 1 and swap_during_trace
                 and out["zero_recompile_during_swap"]
                 and out["p99_held"])
    return out


def ingest_phase(model_path: str, tmp: str, n_requests: int = 200,
                 window: int = 16) -> dict:
    """Native request-ingest A/B (ISSUE 14, docs/serving.md "Native
    request ingest") — two parts over the SAME encoded (PNG) trace.
    PNG because the decode contract there is BITWISE, which upgrades
    "scores row-identical" from a tolerance claim to np.array_equal.

    (1) `ab`: a serial host-side A/B, the bench_data idiom — the
    pre-native per-request chain (PIL decode + resize_center_crop +
    Transformer) vs the native chain exactly as the engine runs it
    (C decode per request + ONE fused native call per `window`
    requests). Serial on the driver thread so the numbers are clean
    host time, not GIL/wall noise from the live threads; decode and
    preprocess timed separately (on a PNG trace both decoders are the
    same zlib work — PR 9 owns the decode A/B on the formats where C
    wins; the PREPROCESS half is what ISSUE 14 adds). Enforced (rc):
    native preprocess img/s >= 2x the PIL path's on the same trace,
    preprocessed rows bitwise-equal; the full-chain img/s is reported
    next to it.

    (2) `live`: the same trace through real engines — the
    CAFFE_NATIVE_DECODE=0 pre-native path, the native window-fused
    path, and a `serve_decoded_cache_mb` warm+replay pair, all under a
    PINNED single-bucket ladder so every dispatch runs the same
    compiled program (mixed ladders differ ~1e-15 per program — PR 7's
    documented cross-program reduction-order variance, not an ingest
    effect). Enforced (rc): SCORES row-identical (bitwise) across all
    passes, the cached replay performs ZERO decode calls
    (counter-asserted against data/decode.py's `decode_calls`) with
    every request a cache hit, full fused/immediate engagement per
    path, and compile_count == warmed_buckets on every engine."""
    import io as _io
    import time as _time

    import numpy as np
    from PIL import Image
    import caffe_mpi_tpu.pycaffe as caffe
    from caffe_mpi_tpu import native
    from caffe_mpi_tpu.data import decode as decode_mod
    from caffe_mpi_tpu.serving import ServingEngine, ingest as ingest_mod

    # one weights file so every engine scores with identical params
    net = caffe.Net(model_path, caffe.TEST)
    weights = os.path.join(tmp, "ingest_w.caffemodel")
    net.save(weights)
    preprocess = dict(mean=np.array([104., 117., 123.], np.float32),
                      raw_scale=255.0, channel_swap=(2, 1, 0))

    # 96x96 uploads into a 16x16-input net: the resize+preprocess chain
    # is fully engaged, like real traffic into a fixed-input deploy net
    rng = np.random.RandomState(4)
    trace = []
    for _ in range(n_requests):
        buf = _io.BytesIO()
        Image.fromarray(rng.randint(0, 256, (96, 96, 3), np.uint8)).save(
            buf, format="PNG")
        trace.append(buf.getvalue())

    native_ok = decode_mod.native_enabled() \
        and native.serve_preprocess_available()
    out = {"requests": n_requests, "native_available": native_ok}
    if not native_ok:
        # degraded build (no .so / no codecs): the A/B is unmeasurable,
        # not failed — serving stays on the bitwise PIL path by design
        out["skipped"] = "native ingest plane unavailable"
        out["ok"] = True
        return out

    # ---- part 1: serial host A/B --------------------------------------
    eng0 = ServingEngine(window_ms=0, start=False)
    model = eng0.load_model("m", model_path, weights, **preprocess)
    os.environ["CAFFE_NATIVE_DECODE"] = "0"
    try:
        t0 = _time.perf_counter()
        pil_raws = [decode_mod.decode_image(b) for b in trace]
        pil_dec_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        pil_rows = [model.preprocess(decode_mod.to_float_image(r))
                    for r in pil_raws]
        pil_pre_s = _time.perf_counter() - t0
    finally:
        os.environ.pop("CAFFE_NATIVE_DECODE", None)
    t0 = _time.perf_counter()
    nat_raws = [decode_mod.decode_image(b) for b in trace]
    nat_dec_s = _time.perf_counter() - t0
    scratch = ingest_mod.RequestIngest()
    nat_rows = []
    t0 = _time.perf_counter()
    for start in range(0, n_requests, window):
        # the batcher's window close, run in series
        rows, errs = ingest_mod.preprocess_rows(
            model, nat_raws[start:start + window], scratch)
        assert not any(errs)
        nat_rows.extend(rows)
    nat_pre_s = _time.perf_counter() - t0
    pre_speedup = pil_pre_s / max(nat_pre_s, 1e-9)
    out["ab"] = {
        "window": window,
        "decode": {
            "pil_img_per_s": round(n_requests / pil_dec_s, 1),
            "native_img_per_s": round(n_requests / nat_dec_s, 1),
        },
        "preprocess": {
            "pil_img_per_s": round(n_requests / pil_pre_s, 1),
            "native_img_per_s": round(n_requests / nat_pre_s, 1),
            "speedup": round(pre_speedup, 2),
        },
        "full_chain": {
            "pil_img_per_s": round(
                n_requests / (pil_dec_s + pil_pre_s), 1),
            "native_img_per_s": round(
                n_requests / (nat_dec_s + nat_pre_s), 1),
            "speedup": round((pil_dec_s + pil_pre_s)
                             / max(nat_dec_s + nat_pre_s, 1e-9), 2),
        },
        "rows_bitwise": bool(np.array_equal(np.stack(pil_rows),
                                            np.stack(nat_rows))),
        "fused_rows": scratch.fused_rows,
    }
    eng0.close()

    # ---- part 2: live engines (counters, parity, cache, recompiles) ---
    def run_pass(cache_mb: float, replay: bool = False):
        # single-bucket ladder: every dispatch runs ONE compiled
        # program, so the cross-pass score comparison is bitwise (see
        # the docstring); the max bucket is the declared deploy batch
        max_bucket = str(model.fwd.ladder[-1])
        eng = ServingEngine(window_ms=WINDOW_MS, buckets=max_bucket,
                            decoded_cache_mb=cache_mb)
        eng.load_model("m", model_path, weights, **preprocess)
        warmed = eng.warmed_buckets

        def one_trace():
            i0 = eng.ingest.stats()
            d0 = decode_mod.STATS.snapshot()["decode_calls"]
            futures = [eng.submit_bytes("m", b) for b in trace]
            eng.drain(timeout=120)
            scores = np.stack([f.result(timeout=1) for f in futures])
            i1 = eng.ingest.stats()
            return {
                "scores": scores,
                "decode_calls": i1["decode_plane"]["decode_calls"] - d0,
                "cache_hits": i1["cache_hits"] - i0["cache_hits"],
                "fused_rows": i1["fused_rows"] - i0["fused_rows"],
                "immediate_rows": (i1["immediate_rows"]
                                   - i0["immediate_rows"]),
            }

        res = one_trace()
        if replay:
            res = {"warm": {k: v for k, v in res.items() if k != "scores"},
                   **one_trace()}
        res["zero_recompile"] = (eng.compile_count == warmed)
        eng.close()
        return res

    os.environ["CAFFE_NATIVE_DECODE"] = "0"
    try:
        pil = run_pass(cache_mb=0)
    finally:
        os.environ.pop("CAFFE_NATIVE_DECODE", None)
    nat = run_pass(cache_mb=0)
    cached = run_pass(cache_mb=64, replay=True)
    out["live"] = {
        "pil": {k: v for k, v in pil.items() if k != "scores"},
        "native": {k: v for k, v in nat.items() if k != "scores"},
        "cached": {k: v for k, v in cached.items() if k != "scores"},
        # PNG trace: decode is bitwise, fused preprocess is bitwise =>
        # the row-parity contract is exact equality, not a tolerance
        "scores_row_identical": bool(
            np.array_equal(pil["scores"], nat["scores"])
            and np.array_equal(pil["scores"], cached["scores"])),
    }
    out["ok"] = (pre_speedup >= 2.0
                 and out["ab"]["rows_bitwise"]
                 and out["live"]["scores_row_identical"]
                 and cached["decode_calls"] == 0
                 and cached["cache_hits"] == n_requests
                 and nat["fused_rows"] == n_requests
                 and pil["immediate_rows"] == n_requests
                 and pil["zero_recompile"] and nat["zero_recompile"]
                 and cached["zero_recompile"])
    return out


def shed_phase(model_path: str, shape, limit: int = 8,
               offered: int = 200) -> dict:
    import numpy as np
    from caffe_mpi_tpu.serving import ServingEngine, ShedError

    # a generous window parks the backlog so admission control — not
    # dispatch speed — decides; accepted requests still all complete
    eng = ServingEngine(window_ms=25, queue_limit=limit)
    eng.load_model("m", model_path)
    rng = np.random.RandomState(3)
    h, w, c = shape
    futures = []
    shed = 0
    for _ in range(offered):
        try:
            futures.append(eng.submit(
                "m", rng.rand(h, w, c).astype(np.float32)))
        except ShedError:
            shed += 1
    eng.drain(timeout=120)
    for f in futures:
        f.result(timeout=1)
    st = eng.stats()
    eng.close()
    out = {
        "queue_limit": limit,
        "offered": offered,
        "accepted": len(futures),
        "shed": shed,
        "max_queue_depth": st["max_queue_depth"],
        "depth_bounded": st["max_queue_depth"] <= limit,
    }
    out["ok"] = (out["depth_bounded"] and shed > 0
                 and shed == st["shed_requests"]
                 and len(futures) + shed == offered)
    return out


def cold_start_phase(paths: dict, shapes: dict, tmp: str) -> dict:
    """Persistent program bank A/B (ISSUE 17): the same two-model zoo
    started three times — bank OFF (fresh-compile baseline), bank COLD
    (first banked run, populates the entries), bank WARM (the restart
    that matters). Enforced (rc): the bank-warm start performs ZERO
    compiles (`compile_count == bank_misses == 0`, every warmed bucket
    a counted hit), its scores on a fixed probe trace are BITWISE equal
    to the fresh-compile engine's (same seed-0 deterministic init, and
    the deserialized executable IS the stored XLA program), and its
    zoo-load wall time beats the fresh-compile baseline."""
    import numpy as np
    from caffe_mpi_tpu.serving import ServingEngine

    bank_dir = os.path.join(tmp, "program_bank")
    rng = np.random.RandomState(5)
    probes = {name: [rng.rand(*shapes[name]).astype(np.float32)
                     for _ in range(4)] for name in paths}

    def start(bank_path):
        eng = ServingEngine(window_ms=0, program_bank=bank_path)
        t0 = time.perf_counter()
        for name in paths:
            eng.load_model(name, paths[name])
        load_ms = (time.perf_counter() - t0) * 1e3
        scores = {name: np.asarray(eng.classify(name, probes[name]))  # lint: ok(host-sync) — classify returns host arrays; two models, boundary-rate
                  for name in paths}
        bank = eng.stats()["bank"]
        out = {
            "load_ms": round(load_ms, 1),
            "cold_start_ms": bank["cold_start_ms"],
            "compiles": eng.compile_count,
            "warmed": eng.warmed_buckets,
            "bank_hits": bank["hits"],
            "bank_misses": bank["misses"],
            "stores": bank["stores"],
            "verify_rejects": bank["verify_rejects"],
        }
        eng.close()
        return out, scores

    fresh, fresh_scores = start(None)
    cold, _ = start(bank_dir)
    warm, warm_scores = start(bank_dir)
    bitwise = all(np.array_equal(fresh_scores[n], warm_scores[n])
                  for n in paths)
    out = {
        "bank_off": fresh,
        "bank_cold": cold,
        "bank_warm": warm,
        "scores_bitwise_bank_vs_fresh": bool(bitwise),
        "speedup": round(fresh["load_ms"] / max(warm["load_ms"], 1e-9), 2),
    }
    out["ok"] = (warm["compiles"] == 0
                 and warm["bank_misses"] == 0
                 and warm["bank_hits"] == warm["warmed"]
                 and cold["compiles"] == cold["bank_misses"]
                 and cold["stores"] == cold["warmed"]
                 and bitwise
                 and warm["load_ms"] < fresh["load_ms"])
    return out


if __name__ == "__main__":
    sys.exit(main())
