"""ctypes binding for the native batch transformer.

Loads libcaffe_tpu_native.so (built by build.sh; the .so is not
committed) and exposes `transform_batch`. `available()` gates callers;
the Python numpy path in data.transformer is the behavioral reference
and fallback. A missing or stale library is said once, in the log — the
PIL/numpy path is several times slower and must not be taken in silence.
"""

from __future__ import annotations

import ctypes
import logging
import os

import numpy as np

_LIB = None
_TRIED = False


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = os.path.join(os.path.dirname(__file__), "libcaffe_tpu_native.so")
    log = logging.getLogger("caffe_mpi_tpu.native")
    if not os.path.exists(path):
        log.warning("native library not built (%s missing): decode and "
                    "transform take the PIL/numpy path; build it with "
                    "caffe_mpi_tpu/native/build.sh", path)
        return None
    lib = ctypes.CDLL(path)
    if lib.caffe_tpu_native_abi_version() != 1:
        log.warning("native library %s has ABI version %d, expected 1: "
                    "ignored (PIL/numpy path); rebuild it with "
                    "caffe_mpi_tpu/native/build.sh", path,
                    lib.caffe_tpu_native_abi_version())
        return None
    lib.caffe_tpu_db_open.restype = ctypes.c_void_p
    lib.caffe_tpu_db_open.argtypes = [ctypes.c_char_p]
    lib.caffe_tpu_db_count.restype = ctypes.c_int64
    lib.caffe_tpu_db_count.argtypes = [ctypes.c_void_p]
    lib.caffe_tpu_db_get.restype = ctypes.c_int
    lib.caffe_tpu_db_get.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    lib.caffe_tpu_db_close.restype = None
    lib.caffe_tpu_db_close.argtypes = [ctypes.c_void_p]
    lib.caffe_tpu_lmdb_open.restype = ctypes.c_void_p
    lib.caffe_tpu_lmdb_open.argtypes = [ctypes.c_char_p]
    lib.caffe_tpu_lmdb_count.restype = ctypes.c_int64
    lib.caffe_tpu_lmdb_count.argtypes = [ctypes.c_void_p]
    lib.caffe_tpu_lmdb_record.restype = ctypes.c_int
    lib.caffe_tpu_lmdb_record.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64)]
    lib.caffe_tpu_lmdb_close.restype = None
    lib.caffe_tpu_lmdb_close.argtypes = [ctypes.c_void_p]
    # added with ISSUE 4; a pre-existing .so without the symbol still
    # loads (python-side crc32c is the fallback)
    try:
        lib.caffe_tpu_lmdb_value_crc32c.restype = ctypes.c_int64
        lib.caffe_tpu_lmdb_value_crc32c.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_int64]
    except AttributeError:
        pass
    # decode plane (ISSUE 10); a pre-existing .so without the symbols
    # still loads (PIL decode is the fallback)
    try:
        lib.caffe_tpu_decode_available.restype = ctypes.c_int
        lib.caffe_tpu_decode_available.argtypes = []
        lib.caffe_tpu_decode_probe.restype = ctypes.c_int
        lib.caffe_tpu_decode_probe.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.caffe_tpu_decode_image.restype = ctypes.c_int
        lib.caffe_tpu_decode_image.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.caffe_tpu_decode_resize.restype = ctypes.c_int
        lib.caffe_tpu_decode_resize.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
        lib.caffe_tpu_decode_transform_batch.restype = ctypes.c_int
        lib.caffe_tpu_decode_transform_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),          # srcs
            ctypes.POINTER(ctypes.c_int64),           # lens
            ctypes.POINTER(ctypes.c_int64),           # record_ids
            ctypes.c_int,                             # n
            ctypes.c_int,                             # crop
            ctypes.c_void_p,                          # mean
            ctypes.c_int, ctypes.c_float,             # mean_mode, scale
            ctypes.c_int, ctypes.c_int,               # train, mirror
            ctypes.c_uint64,                          # seed
            ctypes.c_int, ctypes.c_int,               # out_h, out_w
            ctypes.POINTER(ctypes.c_float),           # out (nullable)
            ctypes.POINTER(ctypes.c_void_p),          # decoded_out (nullable)
            ctypes.POINTER(ctypes.c_int64),           # decoded_caps
            ctypes.POINTER(ctypes.c_int32),           # status
            ctypes.c_int,                             # num_threads
        ]
    except AttributeError:
        pass
    # serving request preprocess (ISSUE 14); a pre-existing .so without
    # the symbol still loads (per-request Python preprocess is the
    # fallback)
    try:
        lib.caffe_tpu_serve_preprocess_batch.restype = ctypes.c_int
        lib.caffe_tpu_serve_preprocess_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),          # srcs
            ctypes.POINTER(ctypes.c_int32),           # dims (h, w pairs)
            ctypes.c_int, ctypes.c_int,               # n, channels
            ctypes.c_int, ctypes.c_int,               # img_h, img_w
            ctypes.c_int, ctypes.c_int,               # crop_h, crop_w
            ctypes.POINTER(ctypes.c_int32),           # swap
            ctypes.c_int, ctypes.c_float,             # has_raw, raw_scale
            ctypes.POINTER(ctypes.c_float),           # mean (nullable)
            ctypes.c_int, ctypes.c_float,             # has_iscale, scale
            ctypes.POINTER(ctypes.c_float),           # out
            ctypes.POINTER(ctypes.c_int32),           # status
            ctypes.c_int,                             # num_threads
        ]
    except AttributeError:
        pass
    lib.caffe_tpu_transform_batch.restype = ctypes.c_int
    lib.caffe_tpu_transform_batch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),          # srcs
        ctypes.POINTER(ctypes.c_int64),           # record_ids
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,  # n c h w
        ctypes.c_int,                             # crop
        ctypes.c_void_p,                          # mean
        ctypes.c_int, ctypes.c_float,             # mean_mode, scale
        ctypes.c_int, ctypes.c_int,               # train, mirror
        ctypes.c_uint64,                          # seed
        ctypes.POINTER(ctypes.c_float),           # out
        ctypes.c_int,                             # num_threads
    ]
    _LIB = lib
    return lib


def available() -> bool:
    return _load() is not None


class NativeDatumDB:
    """mmap'd zero-copy datumfile reader (datumdb.cc); records parsed in C,
    pixel pointers point into the map — no per-record Python work."""

    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library not built; run native/build.sh")
        self._lib = lib
        self._h = lib.caffe_tpu_db_open(path.encode())
        if not self._h:
            raise ValueError(f"{path}: not a readable datumfile")
        self._n = lib.caffe_tpu_db_count(self._h)

    def __len__(self) -> int:
        return self._n

    def get(self, index: int) -> tuple[np.ndarray, int]:
        ptr = ctypes.c_void_p()
        c = ctypes.c_int()
        h = ctypes.c_int()
        w = ctypes.c_int()
        label = ctypes.c_int()
        rc = self._lib.caffe_tpu_db_get(self._h, index, ctypes.byref(ptr),
                                        ctypes.byref(c), ctypes.byref(h),
                                        ctypes.byref(w), ctypes.byref(label))
        if rc != 0:
            raise ValueError(f"record {index}: native parse failed (rc {rc}; "
                             "encoded/float datums use the python reader)")
        size = c.value * h.value * w.value
        arr = np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_uint8)), (size,))
        # copy out of the mmap so the array outlives close()
        return arr.reshape(c.value, h.value, w.value).copy(), label.value

    def close(self) -> None:
        if self._h:
            self._lib.caffe_tpu_db_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


class NativeLMDB:
    """mmap'd LMDB B+tree reader (lmdb_reader.cc): open walks the tree
    once into a key-ordered locator table; per-record access is one C
    call returning pointers into the mapping. data/lmdb_io.py is the
    behavioral reference and fallback."""

    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library not built; run native/build.sh")
        self._lib = lib
        self._h = lib.caffe_tpu_lmdb_open(path.encode())
        if not self._h:
            raise ValueError(f"{path}: not a readable LMDB (native)")
        self._n = lib.caffe_tpu_lmdb_count(self._h)

    def __len__(self) -> int:
        return self._n

    def _locate(self, index: int):
        kp, vp = ctypes.c_void_p(), ctypes.c_void_p()
        kl, vl = ctypes.c_int64(), ctypes.c_int64()
        rc = self._lib.caffe_tpu_lmdb_record(
            self._h, index, ctypes.byref(kp), ctypes.byref(kl),
            ctypes.byref(vp), ctypes.byref(vl))
        if rc != 0:
            raise IndexError(index)
        return kp, kl, vp, vl

    def record(self, index: int) -> tuple[bytes, bytes]:
        kp, kl, vp, vl = self._locate(index)
        # copies out of the mmap so the bytes outlive close()
        return (ctypes.string_at(kp, kl.value),
                ctypes.string_at(vp, vl.value))

    def key(self, index: int) -> bytes:
        """Key bytes only — never touches (or pages in) the value, so a
        key scan over a multi-GB DB costs MBs."""
        kp, kl, _vp, _vl = self._locate(index)
        return ctypes.string_at(kp, kl.value)

    def value(self, index: int) -> bytes:
        _kp, _kl, vp, vl = self._locate(index)
        return ctypes.string_at(vp, vl.value)

    def value_crc32c(self, index: int) -> int | None:
        """crc32c of the value bytes, computed in C over the mmap (no
        bytes copied into Python) — the native half of the read-path
        integrity check. None when the loaded .so predates the
        symbol."""
        fn = getattr(self._lib, "caffe_tpu_lmdb_value_crc32c", None)
        if fn is None:
            return None
        crc = fn(self._h, index)
        if crc < 0:
            raise IndexError(index)
        return int(crc)

    def close(self) -> None:
        if self._h:
            self._lib.caffe_tpu_lmdb_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def transform_batch(images: np.ndarray, record_ids: np.ndarray, *,
                    crop: int = 0, mean: np.ndarray | None = None,
                    scale: float = 1.0, train: bool = True,
                    mirror: bool = False, seed: int = 0,
                    num_threads: int = 4) -> np.ndarray:
    """images: (N,C,H,W) uint8 contiguous. Returns (N,C,oh,ow) float32."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library not built; run native/build.sh")
    images = np.ascontiguousarray(images, np.uint8)
    n, c, h, w = images.shape
    oh = ow = crop if crop else 0
    if not crop:
        oh, ow = h, w
    out = np.empty((n, c, oh, ow), np.float32)
    src_ptrs = (ctypes.c_void_p * n)(*[
        images.ctypes.data + i * c * h * w for i in range(n)])
    rec = np.ascontiguousarray(record_ids, np.int64)
    mean_mode = 0
    mean_ptr = None
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32)
        if mean.ndim == 1 or mean.size == c:
            mean_mode = 1
        else:
            if mean.shape[-2:] != (h, w):
                raise ValueError("full mean must match image size")
            mean_mode = 2
        mean_ptr = mean.ctypes.data_as(ctypes.c_void_p)
    rc = lib.caffe_tpu_transform_batch(
        src_ptrs, rec.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, c, h, w, crop, mean_ptr, mean_mode, scale,
        int(train), int(mirror), seed,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), num_threads)
    if rc != 0:
        raise RuntimeError(f"native transform failed with code {rc}")
    return out


# ---------------------------------------------------------------------------
# Decode plane (ISSUE 10, decode.cc). Status codes match the C enum;
# "not handled natively" statuses (unknown format / unsupported variant /
# codec-less build) map to None returns so callers fall back to PIL —
# geometry/buffer statuses are caller bugs and raise.
# ---------------------------------------------------------------------------

DECODE_OK = 0
DECODE_UNKNOWN_FORMAT = 1
DECODE_ERROR = 2
DECODE_GEOMETRY = 3
DECODE_BUFFER = 4
DECODE_UNAVAILABLE = 5
# statuses that mean "this record is not ours — hand it to PIL"
_DECODE_FALLBACK = (DECODE_UNKNOWN_FORMAT, DECODE_ERROR, DECODE_UNAVAILABLE)


def decode_available() -> bool:
    """True when the loaded .so was built with libjpeg/libpng (the
    decode entry points exist AND were not compiled as stubs)."""
    lib = _load()
    if lib is None or not hasattr(lib, "caffe_tpu_decode_available"):
        return False
    return bool(lib.caffe_tpu_decode_available())


def decode_probe(data: bytes) -> tuple[int, int] | None:
    """Header-only (h, w) of JPEG/PNG bytes; None = not natively
    decodable (decoded output is always 3-channel BGR)."""
    lib = _load()
    h, w = ctypes.c_int(), ctypes.c_int()
    rc = lib.caffe_tpu_decode_probe(data, len(data), ctypes.byref(h),
                                    ctypes.byref(w))
    if rc in _DECODE_FALLBACK:
        return None
    if rc != DECODE_OK:
        raise RuntimeError(f"native decode probe failed with code {rc}")
    return h.value, w.value


def decode_image_native(data: bytes) -> np.ndarray | None:
    """JPEG/PNG bytes -> (3, h, w) planar BGR uint8, or None when the
    record is not natively decodable (caller falls back to PIL)."""
    lib = _load()
    dims = decode_probe(data)
    if dims is None:
        return None
    h, w = dims
    out = np.empty((3, h, w), np.uint8)
    oh, ow = ctypes.c_int(), ctypes.c_int()
    rc = lib.caffe_tpu_decode_image(
        data, len(data), out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.nbytes, ctypes.byref(oh), ctypes.byref(ow))
    if rc in _DECODE_FALLBACK:
        return None
    if rc != DECODE_OK:
        raise RuntimeError(f"native decode failed with code {rc}")
    return out


def decode_resize_native(data: bytes, out_h: int,
                         out_w: int) -> np.ndarray | None:
    """JPEG/PNG bytes -> decode + bilinear resize (cv::resize
    INTER_LINEAR convention, the reference ImageData layer's semantics)
    -> (3, out_h, out_w) planar BGR uint8; None = PIL fallback."""
    lib = _load()
    out = np.empty((3, out_h, out_w), np.uint8)
    rc = lib.caffe_tpu_decode_resize(
        data, len(data), out_h, out_w,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), out.nbytes)
    if rc in _DECODE_FALLBACK:
        return None
    if rc != DECODE_OK:
        raise RuntimeError(f"native decode+resize failed with code {rc}")
    return out


def serve_preprocess_available() -> bool:
    """True when the loaded .so carries the serving window-preprocess
    entry (ISSUE 14). Independent of the codecs: the entry transforms
    already-decoded arrays, so a transform-only build still has it."""
    lib = _load()
    return lib is not None and hasattr(lib,
                                       "caffe_tpu_serve_preprocess_batch")


def serve_preprocess_batch(raws, *, img_h: int, img_w: int, crop_h: int,
                           crop_w: int, swap, raw_scale: float | None = None,
                           mean=None, input_scale: float | None = None,
                           num_threads: int = 4):
    """Window-fused serving preprocess: `raws` is a list of (c, h, w)
    uint8 contiguous planar images (dims may vary per record). Returns
    (out, status): out (n, c, crop_h, crop_w) float32 — each row the
    bitwise Python per-request chain for the same decoded pixels —
    and the (n,) int32 per-record status (0 ok; nonzero rows are
    untouched, the caller preprocesses those records in Python)."""
    lib = _load()
    if lib is None or not hasattr(lib, "caffe_tpu_serve_preprocess_batch"):
        raise RuntimeError("native serve preprocess unavailable; rebuild "
                           "with caffe_mpi_tpu/native/build.sh")
    n = len(raws)
    if n == 0:
        raise ValueError("empty preprocess batch")
    c = int(raws[0].shape[0])
    dims = np.empty(2 * n, np.int32)
    src_ptrs = (ctypes.c_void_p * n)()
    for i, a in enumerate(raws):
        if a.dtype != np.uint8 or a.ndim != 3 or not a.flags.c_contiguous \
                or a.shape[0] != c:
            raise ValueError(f"record {i}: expected contiguous ({c}, h, w) "
                             f"uint8, got {a.dtype} {a.shape}")
        dims[2 * i], dims[2 * i + 1] = a.shape[1], a.shape[2]
        src_ptrs[i] = a.ctypes.data
    swap = np.ascontiguousarray(swap, np.int32)
    if swap.size != c:
        raise ValueError(f"swap must name {c} source planes")
    mean_ptr = None
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32).reshape(-1)
        if mean.size != c:
            raise ValueError("serving fused preprocess needs a per-channel "
                             "mean")
        mean_ptr = mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    out = np.empty((n, c, crop_h, crop_w), np.float32)
    status = np.empty(n, np.int32)
    rc = lib.caffe_tpu_serve_preprocess_batch(
        src_ptrs, dims.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, c, img_h, img_w, crop_h, crop_w,
        swap.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        int(raw_scale is not None),
        float(raw_scale) if raw_scale is not None else 0.0,
        mean_ptr,
        int(input_scale is not None),
        float(input_scale) if input_scale is not None else 0.0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), num_threads)
    if rc != 0:
        raise RuntimeError(f"native serve preprocess rejected (code {rc})")
    return out, status


def decode_transform_batch(bufs: list[bytes], record_ids, *,
                           crop: int = 0, mean: np.ndarray | None = None,
                           scale: float = 1.0, train: bool = True,
                           mirror: bool = False, seed: int = 0,
                           out_h: int, out_w: int,
                           out: np.ndarray | None = None,
                           decoded_out: list[np.ndarray | None] | None = None,
                           num_threads: int = 4):
    """Fused ingestion: decode -> crop -> mirror -> mean/scale -> f32 for
    a range of records in ONE ctypes call (GIL released for the whole
    batch). Augmentation keys and arithmetic are identical to
    transform_batch (shared transform_core.h).

    out: (n, 3, out_h, out_w) float32 to fill, or None for decode-only
    mode (the device-transform staging fill — then out_h/out_w are the
    REQUIRED decoded dims). decoded_out: optional per-record (3, h, w)
    uint8 buffers (each entry may be None) receiving the raw decode —
    the decoded-record cache fill. Returns the (n,) int32 per-record
    status array; rows whose status != DECODE_OK are untouched and the
    caller re-reads those records through the PIL + quarantine path.
    Full-image mean is not expressible here (decoded dims vary per
    record); callers keep such transforms on the per-record path."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library not built; run native/build.sh")
    n = len(bufs)
    srcs = (ctypes.c_char_p * n)(*bufs)
    lens = np.asarray([len(b) for b in bufs], np.int64)
    rec = np.ascontiguousarray(record_ids, np.int64)
    mean_mode = 0
    mean_ptr = None
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32).reshape(-1)
        mean_mode = 1
        mean_ptr = mean.ctypes.data_as(ctypes.c_void_p)
    out_ptr = None
    if out is not None:
        assert out.dtype == np.float32 and out.flags.c_contiguous
        out_ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    dec_ptrs = None
    caps = np.zeros(n, np.int64)
    if decoded_out is not None:
        dec_ptrs = (ctypes.c_void_p * n)()
        for i, buf in enumerate(decoded_out):
            if buf is not None:
                assert buf.dtype == np.uint8 and buf.flags.c_contiguous
                dec_ptrs[i] = buf.ctypes.data
                caps[i] = buf.nbytes
    status = np.empty(n, np.int32)
    rc = lib.caffe_tpu_decode_transform_batch(
        srcs, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        rec.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        crop, mean_ptr, mean_mode, scale, int(train), int(mirror), seed,
        out_h, out_w, out_ptr, dec_ptrs,
        caps.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), num_threads)
    if rc != 0:
        raise RuntimeError(f"native fused decode call rejected (code {rc})")
    return status
