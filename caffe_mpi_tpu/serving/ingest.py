"""Serving request ingest — native decode, window-fused preprocessing,
and the hot-content decoded cache (ISSUE 14).

Reference: python/caffe/io.py load_image + python/caffe/classifier.py
predict preprocess every request one image at a time on the Python
host, and examples/web_demo/app.py pays that per HTTP upload; the
reference's own throughput story keeps decode/transform in C++ threads
(src/caffe/util/io.cpp DecodeDatumToCVMat, data_transformer.cpp:40-118)
— but only for TRAINING. This module closes the serving half of that
gap, the way PR 9 closed the training half:

  * request decode rides the SAME policy + counters module the training
    feeder uses (data/decode.py: `CAFFE_NATIVE_DECODE` 0/1/auto, PIL
    fallback for declines — CMYK JPEG, alpha/16-bit PNG — and corrupt
    bytes surface as PIL's decode error for the HTTP 400 path, never a
    native crash);
  * preprocessing is fused at WINDOW granularity: the batcher hands a
    closed dispatch window's raw decoded images to one GIL-released
    native call (native/decode.cc caffe_tpu_serve_preprocess_batch ->
    transform_core.h serve_preprocess_one), bitwise-identical to the
    per-request `caffe_io.resize_center_crop` + Transformer chain —
    scores stay row-identical to the classic path by construction;
  * a crc32c-keyed decoded-request cache (`serve_decoded_cache_mb`
    ServingParameter knob; the `decoded_cache_mb` machinery applied
    request-side, LRU by CONTENT hash because the same hot image
    arrives under many requests) lets repeats skip decode entirely —
    counter-asserted via data/decode.py's `decode_calls`.

Decoded-request pixel contract: planar CHW, BGR channel order, uint8 —
the decode plane's contract (data/decode.py), so native- and
PIL-decoded requests are interchangeable (PNG bitwise, JPEG <=1 LSB).

Lock discipline (serving/locks.py): the cache and counter locks here
are LEAVES — decode and the native batch call always run OUTSIDE them
(and outside every engine/batcher lock: the batcher materializes rows
before taking any lock, handler threads decode before submit).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict

import numpy as np

from ..data import decode as decode_mod

log = logging.getLogger(__name__)

_STAT_KEYS = ("requests", "cache_hits", "cache_misses", "cache_inserts",
              "cache_evictions", "fused_batches", "fused_rows",
              "fused_fallback_rows", "immediate_rows", "deferred_rows")


def _content_key(data: bytes) -> int:
    """crc32c of the request bytes — hardware-accelerated when
    google_crc32c is installed (it is, CLAUDE.md), the repo's slice-by-8
    table otherwise (data/leveldb_io.py, the DB integrity plane's own
    fallback)."""
    try:
        from google_crc32c import value as _crc
    except ImportError:  # pragma: no cover — baked into this image
        from ..data.leveldb_io import crc32c as _crc
    return _crc(data)


class RequestIngest:
    """Per-engine request-ingest plane: decode (+ hot-content cache) and
    the window-fused preprocess counters. Thread-safe — HTTP handler
    threads decode concurrently while the dispatcher preprocesses."""

    def __init__(self, cache_mb: float = 0.0):
        self.cache_budget = int(float(cache_mb) * 2**20)  # 0 = cache off
        # key -> (encoded bytes, decoded array): the encoded bytes are
        # stored so a HIT is exact-identity, not trust-the-checksum —
        # crc32c is 32 bits (and linear, so collisions are craftable);
        # serving another image's pixels on a collision would be a
        # silent wrong answer. The bytes are small next to the decoded
        # pixels and are charged to the budget.
        self._cache: OrderedDict[int, tuple[bytes, np.ndarray]] = \
            OrderedDict()
        self.cache_bytes = 0
        self._lock = threading.Lock()
        self.decode_s = 0.0
        self.preprocess_s = 0.0
        for k in _STAT_KEYS:
            setattr(self, k, 0)

    def _count(self, key: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, key, getattr(self, key) + n)

    # -- decode + cache -------------------------------------------------
    def decode(self, data: bytes) -> np.ndarray:
        """Encoded request bytes -> (3, h, w) planar BGR uint8 through
        the training decode plane's policy + counters (data/decode.py).
        Cache hits skip decode entirely (zero `decode_calls` movement);
        raises the decoder's error for non-image bytes — the HTTP front
        maps that to a typed 400."""
        self._count("requests")
        key = None
        if self.cache_budget:
            key = _content_key(data)
            with self._lock:
                hit = self._cache.get(key)
                if hit is not None and hit[0] == data:
                    # exact-identity hit: the stored encoded bytes must
                    # MATCH, not merely hash alike — a 32-bit crc32c
                    # collision (craftable: CRC is linear) must decode
                    # the new bytes, never serve another image's pixels
                    self._cache.move_to_end(key)
                    self.cache_hits += 1
                    return hit[1]
                self.cache_misses += 1
        t0 = time.perf_counter()
        arr = np.ascontiguousarray(decode_mod.decode_image(data))
        arr.setflags(write=False)  # one array may serve many requests
        dt = time.perf_counter() - t0
        entry_bytes = arr.nbytes + len(data)
        with self._lock:
            self.decode_s += dt
            if key is not None and entry_bytes <= self.cache_budget:
                old = self._cache.pop(key, None)
                if old is not None:
                    if old[0] == data:
                        # two handler threads raced the same hot miss:
                        # keep the first copy — a blind overwrite would
                        # double-count cache_bytes (phantom bytes would
                        # shrink the effective budget forever)
                        self._cache[key] = old
                        self._cache.move_to_end(key)
                        return arr
                    # crc collision: the newer content wins, the old
                    # entry's bytes are released
                    self.cache_bytes -= old[1].nbytes + len(old[0])
                self._cache[key] = (data, arr)
                self.cache_bytes += entry_bytes
                self.cache_inserts += 1
                while self.cache_bytes > self.cache_budget:
                    _, (odata, oarr) = self._cache.popitem(last=False)
                    self.cache_bytes -= oarr.nbytes + len(odata)
                    self.cache_evictions += 1
        return arr

    # -- telemetry ------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            out = {k: getattr(self, k) for k in _STAT_KEYS}
            out.update({
                "cache_budget_mb": round(self.cache_budget / 2**20, 3),
                "cache_bytes": self.cache_bytes,
                "decode_ms": round(self.decode_s * 1e3, 3),
                "preprocess_ms": round(self.preprocess_s * 1e3, 3),
            })
        # process-wide decode-plane counters (shared with the training
        # feeder): which decoder actually ran — the engagement telemetry
        # `caffe serve -smoke` and chip_smoke.py's serve leg read
        out["decode_plane"] = decode_mod.STATS.snapshot()
        return out


def build_plan(model):
    """Precompute the native window-preprocess spec for one model, or
    None when the model's preprocessing is not expressible in the fused
    kernel (non-image input, != 3 channels, full-image mean, an exotic
    transpose) — such models keep the classic per-request path. The
    availability/engagement gate (`CAFFE_NATIVE_DECODE`, .so present) is
    checked per window in `fused_engaged`, not here: the env is the
    bench A/B lever and tests flip it at runtime."""
    fwd = model.fwd
    in_shape = fwd.input_shape()
    if model.crop_dims is None or len(in_shape) != 4 or in_shape[1] != 3:
        return None
    t = model.transformer
    in_blob = fwd.input_blob()
    if t.transpose.get(in_blob) != (2, 0, 1):
        return None
    swap_rgb = t.channel_swap.get(in_blob, (0, 1, 2))
    if sorted(swap_rgb) != [0, 1, 2]:
        return None
    mean = t.mean.get(in_blob)
    if mean is not None:
        mean = np.asarray(mean, np.float32)
        if mean.size != 3:  # full-image mean: dims vary per request
            return None
        mean = mean.reshape(3)
    img_h, img_w = (int(d) for d in model.image_dims)
    crop_h, crop_w = (int(d) for d in model.crop_dims)
    if crop_h > img_h or crop_w > img_w:
        return None
    return {
        "img_h": img_h, "img_w": img_w, "crop_h": crop_h, "crop_w": crop_w,
        # decoded storage is BGR planar; the Transformer's channel_swap
        # is spelled over the RGB float image — compose them so output
        # channel j reads storage plane swap[j]
        "swap": np.asarray([2 - s for s in swap_rgb], np.int32),
        "raw_scale": t.raw_scale.get(in_blob),
        "mean": mean,
        "input_scale": t.input_scale.get(in_blob),
    }


def fused_engaged(model) -> bool:
    """True when this model's deferred requests will preprocess through
    the native fused kernel RIGHT NOW: the model has a plan, the .so
    carries the entry, and `CAFFE_NATIVE_DECODE` is not forcing the
    bitwise pre-native path."""
    if getattr(model, "ingest_plan", None) is None:
        return False
    if decode_mod.native_mode() < 0:
        return False
    from .. import native
    return native.available() and native.serve_preprocess_available()


def preprocess_rows(model, raws: list, ingest: RequestIngest,
                    num_threads: int = 0):
    """Window-fused preprocessing for one closed dispatch window:
    `raws` are decoded (3, h, w) BGR uint8 images (dims may vary).
    Returns (rows, errs) aligned with `raws` — rows are the model's f32
    input rows, errs per-record exceptions (a bad record fails only its
    own future, never the co-batched ones). One GIL-released native
    call for the whole window when engaged; per-record declines and the
    `CAFFE_NATIVE_DECODE=0` path run the classic Python chain, which
    the native kernel matches BITWISE (tests/test_serving_ingest.py)."""
    n = len(raws)
    rows: list = [None] * n
    errs: list = [None] * n
    t0 = time.perf_counter()
    plan = getattr(model, "ingest_plan", None)
    if plan is not None and fused_engaged(model):
        from .. import native
        try:
            out, status = native.serve_preprocess_batch(
                raws, img_h=plan["img_h"], img_w=plan["img_w"],
                crop_h=plan["crop_h"], crop_w=plan["crop_w"],
                swap=plan["swap"], raw_scale=plan["raw_scale"],
                mean=plan["mean"], input_scale=plan["input_scale"],
                # ~0.05 ms of C per record: below ~8 records a spawned
                # thread costs more than it saves (measured 12.9 ms
                # single-thread vs 47.5 ms at one-thread-per-record for
                # 200 records in 9-record windows on this 24-core host)
                num_threads=num_threads or max(
                    1, min(n // 8, os.cpu_count() or 4)))
        # lint: ok(typed-failure) — the batch-level reject falls back
        # per record below, where the offender alone fails TYPED (400)
        except Exception:  # noqa: BLE001 — a batch-level reject (bad
            # array) falls back per record below, where the offender
            # fails alone
            log.exception("serving ingest: fused native preprocess "
                          "rejected a window; preprocessing per record")
            out, status = None, None
        if status is not None:
            fused = 0
            for i in range(n):
                if status[i] == 0:
                    rows[i] = out[i]
                    fused += 1
            ingest._count("fused_rows", fused)
            ingest._count("fused_batches")
    for i in range(n):
        if rows[i] is not None:
            continue
        try:
            rows[i] = model.preprocess(decode_mod.to_float_image(raws[i]))
            ingest._count("fused_fallback_rows")
        except Exception as e:  # noqa: BLE001 — goes to this request's
            errs[i] = e        # future only
    dt = time.perf_counter() - t0
    with ingest._lock:
        ingest.preprocess_s += dt
    return rows, errs
