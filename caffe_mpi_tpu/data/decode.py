"""Host image-decode plane — policy, counters, and the PIL fallback for
the native JPEG/PNG decoder (ISSUE 10).

Reference: src/caffe/util/io.cpp DecodeDatumToCVMat (encoded Datum ->
cv::Mat, BGR) and ReadImageToCVMat (file -> optional is_color/resize ->
cv::Mat), both called per record from the C++ reader/transformer threads
(data_reader.cpp, data_transformer.cpp:40-118). The TPU-native design
moves the same work into native/decode.cc behind ctypes — the last
Python-held stage of the host pipeline — while this module owns:

  * the engagement policy: `CAFFE_NATIVE_DECODE` env — "0" forces the
    PIL path (bitwise-identical to the pre-native pipeline), "1" forces
    native (raising when the library is unbuilt — the A/B switch for
    tools/bench_data), unset = native when available;
  * the PIL fallback, which is also the behavioral reference: records
    the native plane declines (exotic variants: CMYK JPEG, alpha/16-bit
    PNG, GIF/BMP/...) decode here, so coverage never shrinks;
  * decode telemetry (`STATS`): per-path record counters read by
    tools/bench_data's stage breakdown and tools/e2e_lmdb_train's run
    journal — and the counter the decoded-record cache tests assert
    against (epoch 2 must decode NOTHING).

Pixel contract everywhere: planar CHW, BGR channel order, uint8 —
matching the reference's OpenCV decode (datasets.parse_datum's
documented parity). PNG parity with PIL is bitwise (lossless format);
JPEG parity is within 1 LSB per pixel (IDCT variance between libjpeg
builds; on this image both link libjpeg-turbo and agree bitwise —
tests/test_native_decode.py pins the contract, docs/benchmarks.md
"Ingestion" documents it).
"""

from __future__ import annotations

import io
import os
import threading

import numpy as np


class DecodeStats:
    """Thread-safe decode-plane counters (Feeder pool workers decode
    concurrently; the cache tests need exact counts, not telemetry-grade
    approximations)."""

    _KEYS = ("native_records", "pil_records", "native_fallbacks",
             "fused_batches", "fused_records", "fused_fallback_records",
             "cache_hits", "cache_inserts", "cache_bytes")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            for k in self._KEYS:
                setattr(self, k, 0)

    def count(self, key: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, key, getattr(self, key) + n)

    def snapshot(self) -> dict:
        with self._lock:
            out = {k: getattr(self, k) for k in self._KEYS}
        # total image decodes actually performed, any path — per-record
        # native, per-record PIL, or inside a fused native batch (cache
        # hits perform none: the epoch-2 zero-decode assertion reads this)
        out["decode_calls"] = (out["native_records"] + out["pil_records"]
                               + out["fused_records"])
        return out


STATS = DecodeStats()


def native_mode() -> int:
    """CAFFE_NATIVE_DECODE policy: -1 forced PIL ("0"), +1 forced native
    ("1"), 0 auto (unset/other). Read per call — it is the bench A/B
    switch and tests flip it at runtime; the getenv cost is noise next
    to a decode."""
    v = os.environ.get("CAFFE_NATIVE_DECODE", "").strip()
    if v == "0":
        return -1
    if v == "1":
        return 1
    return 0


def native_enabled() -> bool:
    """True when records should try the native decoder first."""
    mode = native_mode()
    if mode < 0:
        return False
    from .. import native
    ok = native.available() and native.decode_available()
    if mode > 0 and not ok:
        raise RuntimeError(
            "CAFFE_NATIVE_DECODE=1 but the native decode plane is "
            "unavailable — build it with caffe_mpi_tpu/native/build.sh "
            "(requires libjpeg/libpng dev headers)")
    return ok


def _pil_decode(data: bytes) -> np.ndarray:
    """The reference path: PIL RGB -> BGR CHW (datasets.parse_datum's
    original decode, kept verbatim as fallback + behavioral oracle)."""
    from PIL import Image
    img = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    STATS.count("pil_records")
    # PIL gives RGB HWC; Caffe stores BGR — convert for parity with
    # the reference's OpenCV decode (io.cpp DecodeDatumToCVMat)
    return img[:, :, ::-1].transpose(2, 0, 1)


def decode_image(data: bytes) -> np.ndarray:
    """Encoded image bytes -> (3, h, w) planar BGR uint8. Native when
    enabled and the record is expressible there, else PIL; raises (PIL's
    decode error) when the bytes are no image at all — the caller
    (datasets._decode_verified / materialize_datum) converts that to
    RecordIntegrityError for the quarantine plane."""
    if native_enabled():
        from .. import native
        arr = native.decode_image_native(data)
        if arr is not None:
            STATS.count("native_records")
            return arr
        STATS.count("native_fallbacks")
    return _pil_decode(data)


def to_float_image(arr: np.ndarray) -> np.ndarray:
    """(3, h, w) planar BGR uint8 (this plane's pixel contract) -> HWC
    RGB float32 in [0,1] — the pycaffe load_image / web-upload
    convention. Bitwise what PIL's own decode-and-convert would produce
    for the same pixels (u8 -> f32 is exact, /255.0 is one IEEE divide),
    so callers can decode natively and still feed the classic float
    surfaces (ISSUE 14's serving fallback path, caffe_io.load_image)."""
    if arr.ndim != 3 or arr.shape[0] != 3:
        raise ValueError(f"expected (3, h, w) BGR uint8, got {arr.shape}")
    return arr[::-1].transpose(1, 2, 0).astype(np.float32) / 255.0


def decode_file(data: bytes, *, is_color: bool = True, new_h: int = 0,
                new_w: int = 0) -> np.ndarray:
    """File-read image bytes -> CHW uint8, with the ImageData layer's
    optional bilinear resize (reference io.cpp ReadImageToCVMat). The
    native path covers the color case — resize follows the reference's
    cv::resize INTER_LINEAR convention, where PIL's BILINEAR antialiases
    on downscale — grayscale stays on PIL (the "L" luma weights)."""
    if is_color and native_enabled():
        from .. import native
        if new_h and new_w:
            arr = native.decode_resize_native(data, new_h, new_w)
        else:
            arr = native.decode_image_native(data)
        if arr is not None:
            STATS.count("native_records")
            return arr
        STATS.count("native_fallbacks")
    from PIL import Image
    img = Image.open(io.BytesIO(data))
    img = img.convert("RGB" if is_color else "L")
    if new_h and new_w:
        img = img.resize((new_w, new_h), Image.BILINEAR)
    arr = np.asarray(img)
    STATS.count("pil_records")
    if arr.ndim == 2:
        return arr[None, :, :]
    return arr[:, :, ::-1].transpose(2, 0, 1)  # RGB HWC -> BGR CHW
