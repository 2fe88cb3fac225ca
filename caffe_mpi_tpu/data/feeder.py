"""Batch feeder — host-side prefetch pipeline.

Reference machinery being replaced (SURVEY §2.5): DataReader's reader+parser
threads with per-solver round-robin record distribution
(CursorManager, data_reader.hpp:28-53), BasePrefetchingDataLayer's
transformer threads with free/full Batch queues (base_data_layer.hpp:100-159),
and the GPU-side async batch copy.

TPU-native shape: batches are assembled by a thread pool *ahead of* the
training loop (lookahead window = the free/full queue depth), and the jitted
step overlaps host->HBM transfer with compute because feeds for step N+1 are
device_put while step N runs. Record->rank assignment is a pure index
calculation: global record index for (iteration, slot) is
  it * global_batch + rank * batch + slot  (mod dataset size)
which reproduces CursorManager's deterministic striping without cursors.
Epoch shuffling uses a seed-fixed permutation per epoch (DataCache shuffle,
data_reader.hpp:55-101).

Multi-host (ISSUE 11): under `caffe train -hosts N` the CLI passes
rank = jax.process_index() and world = jax.process_count(), so the same
formula IS the per-host record sharding — disjoint, exhaustive, and a
pure function of (iteration, rank, slot), which keeps crc verification
and quarantine substitution replay-identical on every host and across
supervised restarts. Each host journals quarantines to its own
`<prefix>.quarantine.r<k>.json` (resilience.quarantine_journal_path);
rank 0 merges them at snapshot time.
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

from ..utils.resilience import (FAULTS, QUARANTINE, DataIntegrityError,
                                RecordIntegrityError, retrying)
from .datasets import Dataset, DecodedCacheDataset
from .transformer import DataTransformer

log = logging.getLogger("caffe_mpi_tpu.feeder")

_LOOKAHEAD_HARD_CAP = 16  # queue-depth ceiling even with RAM to spare
# quarantine plane (ISSUE 4): how many successive substitute records to
# probe past a corrupt one before declaring the neighborhood dead, and
# the distinct-record bound past which corruption counts as systematic
# (dataset-level) rather than record-level
_QUARANTINE_PROBES = 16
_QUARANTINE_MAX_FRACTION = 0.05


class FeedError(RuntimeError):
    """A device feed super-batch failed to assemble. Carries the
    originating (it0, k) chunk so the crash names the exact batch —
    the bare Future exception used to surface with no context (or, in
    the abandoned-hint path, not at all) and the solver stalled."""


def _default_mem_budget() -> int:
    """Host-RAM budget for in-flight batches: 25% of physical memory,
    capped at 2 GiB (the reference sizes its queue from free *GPU*
    memory, data_layer.cpp:66-77; here batches live in host RAM until
    device_put)."""
    try:
        phys = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        phys = 8 << 30
    if phys <= 0:  # sysconf can return -1 for "name known, no value"
        phys = 8 << 30
    return min(phys // 4, 2 << 30)


class Feeder:
    def __init__(self, dataset: Dataset, transformer: DataTransformer | None,
                 batch_size: int, *, rank: int = 0, world: int = 1,
                 shuffle: bool = False, seed: int = 0, threads: int = 0,
                 lookahead: int = 3, to_device=None,
                 top_names: tuple[str, str] = ("data", "label"),
                 device_transform: bool = False,
                 mem_budget: int | None = None):
        """to_device: optional callable(feeds_dict) -> feeds_dict placing
        arrays (e.g. MeshPlan.shard_feeds); applied on the consumer side.
        top_names: blob names for the (image, label) tops — from the data
        layer's prototxt `top:` entries.
        device_transform: stage raw uint8 batches + per-record aug
        decisions instead of transforming on the host — must match the
        consuming Net's DataLayer.dev_transform (the CLI binds both from
        the net; see layers/data_layers.py).
        threads=0 (the prototxt default) enables AUTO mode, mirroring the
        reference's iteration-0 prefetch auto-tuning
        (data_layer.cpp:46-113): worker count defaults to the host core
        count and the lookahead window is re-sized at runtime from the
        measured batch-build time vs the consumer's step time, bounded by
        `mem_budget` bytes of in-flight batches. An explicit threads>0
        pins both knobs (reference: explicit threads+parser_threads)."""
        self.top_names = top_names
        self.ds = dataset
        self.tf = transformer
        self.batch = batch_size
        self.rank = rank
        self.world = world
        self.shuffle = shuffle
        self.seed = seed
        self.lookahead = max(lookahead, 1)
        self.to_device = to_device
        self.auto = threads == 0
        if self.auto:
            threads = min(os.cpu_count() or 2, 8)
        self.threads = max(threads, 1)
        self.device_transform = device_transform
        self.mem_budget = (_default_mem_budget() if mem_budget is None
                           else mem_budget)
        # auto-tune telemetry: build durations (producer side), consumer
        # gaps (time spent OUTSIDE __call__ = the training step), and the
        # realized batch footprint
        self._build_times: deque[float] = deque(maxlen=32)
        self._gaps: deque[float] = deque(maxlen=32)
        self._last_exit: float | None = None
        self._calls = 0
        self._batch_bytes = 0
        # native C++ transform path: engaged when built and the transform is
        # expressible there (no force_color/gray); per-batch uniform-shape
        # uint8 checked at run time, python path as fallback
        self._native = False
        if transformer is not None:
            from .. import native
            tp = transformer.tp
            self._native = (native.available() and not tp.force_color
                            and not tp.force_gray)
        # fused native ingestion (ISSUE 10): for JPEG/PNG-encoded
        # datasets, decode -> crop -> mirror -> mean/scale -> f32 (or
        # decode-only, in device-transform staging mode) runs for the
        # whole batch in ONE ctypes call with the GIL released
        # (native/decode.cc), instead of one PIL decode per record under
        # the interpreter lock. None = undecided until the first batch
        # reveals whether the dataset carries encoded records; False =
        # permanently on the classic path (raw/float datums — bitwise
        # today's behavior, decided once so raw datasets never pay a
        # re-probe).
        self._fused_ok: bool | None = None
        if getattr(dataset, "get_datum", None) is None:
            self._fused_ok = False  # no wire-fields API (synthetic, image
            #                         folder, cached) — per-record path
        elif device_transform:
            pass  # fused decode-only staging fill
        elif transformer is None or not self._native:
            self._fused_ok = False  # transform not expressible natively
        elif (transformer.mean is not None
              and transformer.mean.reshape(-1).size not in (1, 3)):
            # full-image mean needs the per-record crop window at the
            # image's own dims, which vary per encoded record; sizes 1/3
            # broadcast over the decoder's fixed 3 BGR channels. Decided
            # HERE so an inexpressible mean never pays the fused fetch
            # just to bail per batch.
            self._fused_ok = False
        self.pool = ThreadPoolExecutor(max_workers=max(threads, 1))
        self._futures: dict[int, Future] = {}
        self._lock = threading.Lock()
        # batch builds currently executing (pool workers + direct
        # callers) — sizes the fused decode's inner thread count so
        # worker-count x per-call threads never oversubscribes the host
        self._inflight = 0
        n = len(dataset)
        if n == 0:
            raise ValueError("empty dataset")
        self._size = n
        self._perm_cache: dict[int, np.ndarray] = {}
        # quarantine plane: distinct corrupt records substituted so far
        # (set membership drives the bounded-ratio hard failure);
        # guarded by _lock — pool workers quarantine concurrently
        self._quarantined: set[int] = set()
        # rec -> substitute memo: substitution is a pure function of
        # the record index, so after the first discovery later epochs
        # read the substitute directly (no re-read + re-checksum of
        # the known-corrupt record, no re-probing)
        self._sub_cache: dict[int, int] = {}
        self._quarantine_limit = max(4, int(n * _QUARANTINE_MAX_FRACTION))

    # ------------------------------------------------------------------
    def _record_index(self, it: int, slot: int) -> int:
        flat = it * self.batch * self.world + self.rank * self.batch + slot
        epoch, within = divmod(flat, self._size)
        if not self.shuffle:
            return within
        perm = self._perm_cache.get(epoch)
        if perm is None:
            perm = np.random.RandomState(self.seed + epoch).permutation(self._size)
            with self._lock:
                self._perm_cache[epoch] = perm
                # bound the cache
                for k in sorted(self._perm_cache):
                    if k < epoch - 2:
                        del self._perm_cache[k]
        return int(perm[within])

    def _decode_threads(self) -> int:
        """Threads for ONE fused native decode call. An explicitly
        pinned feeder keeps its pin (operator's choice, like the classic
        native transform); auto mode divides the host's cores across the
        builds in flight — 8 workers each spawning 8 decode threads is
        the documented oversubscription collapse, not a speedup."""
        if not self.auto:
            return self.threads
        with self._lock:
            inflight = max(self._inflight, 1)
        return max(1, (os.cpu_count() or 1) // inflight)

    def _build_batch(self, it: int) -> dict[str, np.ndarray]:
        t0 = time.perf_counter()
        with self._lock:
            self._inflight += 1
        try:
            out = self._build_batch_inner(it)
        finally:
            with self._lock:
                self._inflight -= 1
        if self.auto:
            # pool worker threads append concurrently with the consumer's
            # retune scan — both sides take the lock
            with self._lock:
                self._build_times.append(time.perf_counter() - t0)
                if not self._batch_bytes:
                    self._batch_bytes = sum(
                        v.nbytes for v in out.values()
                        if isinstance(v, np.ndarray)) or 1
        return out

    def _read_record(self, rec: int):
        """One dataset read with bounded-backoff retry: transient I/O
        errors (NFS blips, DB cursor hiccups — and the injected
        `feeder_read` fault) are absorbed up to the attempt budget; a
        persistent failure surfaces to the consumer with the record
        named, where the supervisor owns the restart. Integrity
        failures (RecordIntegrityError — crc mismatch, structural DB
        rot, undecodable Datum) are DETERMINISTIC: they bypass the
        retry budget and quarantine instead (_read_record_verified)."""
        def get():
            FAULTS.maybe_raise("feeder_read", OSError,
                               f"injected dataset read fault (record {rec})")
            return self.ds.get(rec)
        return retrying(get, attempts=4, base_delay=0.05,
                        desc=f"dataset read (record {rec})")

    def _fetch_datum(self, rec: int):
        """One wire-fields fetch (no image materialization) with the
        same bounded-backoff retry + fault site as _read_record — the
        fused path's fetch stage."""
        def get():
            FAULTS.maybe_raise("feeder_read", OSError,
                               f"injected dataset read fault (record {rec})")
            return self.ds.get_datum(rec)
        return retrying(get, attempts=4, base_delay=0.05,
                        desc=f"dataset read (record {rec})")

    def _read_record_verified(self, rec: int):
        return self._verified(rec, self._read_record)

    def _verified(self, rec: int, read_fn):
        """Read record `rec` via `read_fn`, quarantining it on an
        integrity failure (ISSUE 4): the substitute is the next healthy
        record by index — `(rec + probe) % size`, probe = 1.. — a pure
        function of `rec` (itself a pure function of the iteration
        index), so a resumed or replayed run makes IDENTICAL
        substitution decisions and stays iteration-exact. Each newly
        quarantined record is journaled to `<prefix>.quarantine.json`;
        more than `_quarantine_limit` distinct corrupt records (or a
        fully corrupt probe window) is systematic corruption and raises
        DataIntegrityError — a hard, named failure instead of silently
        training on substitutes.

        `read_fn` is _read_record (full materialization) or
        _fetch_datum (wire fields only, the fused path — decode
        failures there re-enter through _read_record_verified, which
        may add the rotten substitute to the journal one step later
        than the classic path would have; the substitution function
        itself is identical)."""
        sub = self._sub_cache.get(rec)
        if sub is not None:
            # recurse: if the memoized substitute has ITSELF rotted
            # since, it gets quarantined like any primary record
            # (depth bounded by the quarantine limit)
            return self._verified(sub, read_fn)
        try:
            return read_fn(rec)
        except RecordIntegrityError as first:
            src = getattr(self.ds, "path", "") or type(self.ds).__name__
            with self._lock:
                self._quarantined.add(rec)
                n_bad = len(self._quarantined)
            if n_bad > self._quarantine_limit:
                raise DataIntegrityError(
                    f"{n_bad} distinct corrupt records in {src} exceeds "
                    f"the quarantine bound ({self._quarantine_limit} = "
                    f"{_QUARANTINE_MAX_FRACTION:.0%} of {self._size}); "
                    "corruption is systematic — regenerate the dataset "
                    f"(first failure: {first})") from first
            for probe in range(1, _QUARANTINE_PROBES + 1):
                sub = (rec + probe) % self._size
                try:
                    out = read_fn(sub)
                except RecordIntegrityError as e:
                    with self._lock:
                        self._quarantined.add(sub)
                    # probe casualties count toward the systematic
                    # bound, so they must appear in the audit journal
                    # too (substitute -1 = "skipped during probing")
                    QUARANTINE.record(src, sub, -1, e.reason)
                    continue
                QUARANTINE.record(src, rec, sub, first.reason)
                with self._lock:
                    self._sub_cache[rec] = sub
                return out
            raise DataIntegrityError(
                f"records {rec}..{(rec + _QUARANTINE_PROBES) % self._size}"
                f" of {src} are ALL corrupt ({_QUARANTINE_PROBES + 1} "
                "consecutive); corruption is systematic — regenerate "
                f"the dataset (first failure: {first})") from first

    def _assemble(self, raws: list[np.ndarray], labels: list[int],
                  flats: list[int]) -> dict[str, np.ndarray]:
        """Shared batch tail: transform/stage + label top."""
        if self.device_transform:
            out = self._raw_batch(raws, flats)
        else:
            out = {self.top_names[0]: self._transform(raws, flats)}
        if len(self.top_names) > 1:
            out[self.top_names[1]] = np.asarray(labels, np.int32)
        return out

    def _build_batch_inner(self, it: int) -> dict[str, np.ndarray]:
        if self._fused_ok is not False:
            from . import decode as _decode
            if _decode.native_enabled():
                out = self._build_batch_fused(it)
                if out is not None:
                    return out
        raws, labels, flats = [], [], []
        for slot in range(self.batch):
            rec = self._record_index(it, slot)
            img, label = self._read_record_verified(rec)
            raws.append(img)
            labels.append(label)
            flats.append(it * self.batch * self.world
                         + self.rank * self.batch + slot)
        return self._assemble(raws, labels, flats)

    # -- fused native ingestion (ISSUE 10) ------------------------------
    def _build_batch_fused(self, it: int) -> dict[str, np.ndarray] | None:
        """Batch build for encoded datasets: fetch verified wire fields
        per record, then decode JPEG/PNG payloads for the WHOLE batch in
        one GIL-released native call — fused with the transform
        (host-transform mode) or decoding straight into the uniform
        uint8 staging stack (device-transform mode). Cache hits
        (DecodedCacheDataset) skip decode entirely; records the native
        decoder declines fall back one-at-a-time through the classic
        read path, which owns PIL fallback and quarantine. Augmentation
        keys (seed ^ flat-index splitmix64) and the transform arithmetic
        are shared with the classic native path (transform_core.h), so
        engagement changes WHICH decoder ran, never the aug decisions or
        the record->rank striping.

        Returns None exactly once, when the first batch shows the
        dataset has no encoded records — then the Feeder pins itself to
        the classic path (`_fused_ok = False`) and never re-probes."""
        from . import decode as _decode
        from .. import native

        cache = self.ds if isinstance(self.ds, DecodedCacheDataset) else None
        recs, flats = [], []
        for slot in range(self.batch):
            recs.append(self._record_index(it, slot))
            flats.append(it * self.batch * self.world
                         + self.rank * self.batch + slot)
        # per slot: ("enc", jpeg/png bytes, label) | ("arr", CHW, label)
        entries: list[tuple] = []
        for rec in recs:
            hit = cache.lookup(rec) if cache is not None else None
            if hit is not None:
                entries.append(("arr", hit[0], hit[1]))
                continue
            fields = self._verified(rec, self._fetch_datum)
            if fields.encoded:
                entries.append(("enc", fields.data, fields.label))
            else:
                # raw/float datum: materialize in place (identical to
                # what ds.get(rec) would have returned)
                from .datasets import materialize_datum
                try:
                    arr, label = materialize_datum(fields)
                except Exception:
                    arr, label = self._read_record_verified(rec)
                entries.append(("arr", arr, label))
        if self._fused_ok is None:
            self._fused_ok = any(e[0] == "enc" for e in entries)
            if not self._fused_ok:
                # not an encoded dataset: assemble this batch from the
                # already-fetched records (bitwise-identical tail) and
                # stay classic forever
                return self._assemble([e[1] for e in entries],
                                      [e[2] for e in entries], flats)
        enc = [i for i, e in enumerate(entries) if e[0] == "enc"]
        if self.device_transform:
            out = self._fused_staging(entries, enc, recs, flats, cache)
        else:
            out = self._fused_transform(entries, enc, recs, flats, cache)
        if out is not None and enc:
            # fused_records is counted per SUCCESSFUL record inside the
            # helpers (statuses in hand) — a declined record must show
            # up as a PIL fallback, not a native decode, or the
            # --require-native-decode assertion would pass on a run
            # that silently fell back wholesale
            _decode.STATS.count("fused_batches")
        return out

    def _fallback_record(self, slot_rec: int):
        """Per-record fallback for payloads the native decoder declined
        (exotic variant or corrupt bytes): the classic verified read
        decodes via PIL and owns quarantine."""
        from . import decode as _decode
        _decode.STATS.count("fused_fallback_records")
        return self._read_record_verified(slot_rec)

    def _fused_transform(self, entries, enc, recs, flats, cache):
        """Host-transform mode: one native call decodes + transforms all
        encoded slots into their f32 rows (per-record decoded dims may
        vary when cropping — the C side crops each at its own size)."""
        from .. import native
        if not enc:
            # nothing to decode (all cache hits / raw slots): the classic
            # tail IS the fast path — one native transform_batch over the
            # stacked uint8 records, no staging array or scatter
            return self._assemble([e[1] for e in entries],
                                  [e[2] for e in entries], flats)
        tf = self.tf
        crop = tf.tp.crop_size
        n = len(entries)
        labels = [e[2] for e in entries]
        if crop:
            oh = ow = crop
        else:
            # no crop: output dims = decoded dims, which must be uniform
            first = entries[0]
            if first[0] == "arr":
                oh, ow = first[1].shape[-2:]
            else:
                dims = native.decode_probe(first[1])
                if dims is None:
                    arr, labels[0] = self._fallback_record(recs[0])
                    entries[0] = ("arr", arr, labels[0])
                    oh, ow = arr.shape[-2:]
                else:
                    oh, ow = dims
            enc = [i for i in enc if entries[i][0] == "enc"]
        mean = tf.mean
        if mean is not None:
            mean = mean.reshape(-1)  # per-channel (c,1,1)/(c,) -> (c,)
            if mean.size == 1:
                # single mean_value applies to every channel (reference
                # data_transformer.cpp: mean_values_ repeated)
                mean = np.repeat(mean, 3)
        out = np.empty((n, 3, oh, ow), np.float32)
        seed = tf.seed or 0
        train = tf.phase == "TRAIN"
        if enc:
            bufs = [entries[i][1] for i in enc]
            ids = np.asarray([flats[i] for i in enc], np.int64)
            # whole-batch encoded (the common case): the C call writes
            # each record's f32 row straight into `out` — no staging
            # array, no scatter copy. Mixed batches (cache hits / raw
            # slots interleaved) stage the encoded subset and scatter.
            whole = len(enc) == n
            enc_out = out if whole else np.empty((len(enc), 3, oh, ow),
                                                 np.float32)
            decoded = None
            if cache is not None and cache.admitting():
                decoded = []
                for b in bufs:
                    dims = native.decode_probe(b)
                    decoded.append(None if dims is None else
                                   np.empty((3, *dims), np.uint8))
            status = native.decode_transform_batch(
                bufs, ids, crop=crop, mean=mean, scale=tf.tp.scale,
                train=train, mirror=tf.tp.mirror, seed=seed,
                out_h=oh, out_w=ow, out=enc_out, decoded_out=decoded,
                num_threads=self._decode_threads())
            from . import decode as _decode
            for k, i in enumerate(enc):
                if status[k] == native.DECODE_OK:
                    _decode.STATS.count("fused_records")
                    if not whole:
                        out[i] = enc_out[k]
                    if decoded is not None and decoded[k] is not None:
                        cache.insert(recs[i], decoded[k], labels[i])
                else:
                    # failed rows left garbage in `out`; the fallback
                    # re-read below rewrites them via the "arr" pass
                    arr, labels[i] = self._fallback_record(recs[i])
                    entries[i] = ("arr", arr, labels[i])
        # cache hits, raw records, and fallbacks: the classic transform
        # (native batch call per uniform-shape group, python otherwise)
        rest = [i for i in range(len(entries)) if entries[i][0] == "arr"]
        if rest:
            shapes = {entries[i][1].shape for i in rest}
            dtypes = {entries[i][1].dtype for i in rest}
            if len(shapes) == 1 and dtypes == {np.dtype(np.uint8)}:
                rows = self._transform([entries[i][1] for i in rest],
                                       [flats[i] for i in rest])
                for k, i in enumerate(rest):
                    out[i] = rows[k]
            else:
                for i in rest:
                    out[i] = self._transform([entries[i][1]], [flats[i]])[0]
        res = {self.top_names[0]: out}
        if len(self.top_names) > 1:
            res[self.top_names[1]] = np.asarray(labels, np.int32)
        return res

    def _fused_staging(self, entries, enc, recs, flats, cache):
        """Device-transform mode: decode encoded slots straight into the
        uniform uint8 staging stack (the in-graph transform consumes raw
        records + aug decisions; reference use_gpu_transform)."""
        from .. import native
        from .device_transform import aug_key, compute_aug
        n = len(entries)
        labels = [e[2] for e in entries]
        first = entries[0]
        if first[0] == "arr":
            shape = first[1].shape
        else:
            dims = native.decode_probe(first[1])
            if dims is None:
                arr, labels[0] = self._fallback_record(recs[0])
                entries[0] = ("arr", arr, labels[0])
                shape = arr.shape
            else:
                shape = (3, *dims)
            enc = [i for i in enc if entries[i][0] == "enc"]
        if len(shape) != 3 or shape[0] != 3:
            return None  # encoded records decode to 3xHxW; mismatch ->
            #              classic path handles (and errors) as before
        stack = np.empty((n, *shape), np.uint8)
        if enc:
            bufs = [entries[i][1] for i in enc]
            ids = np.asarray([flats[i] for i in enc], np.int64)
            status = native.decode_transform_batch(
                bufs, ids, out_h=shape[1], out_w=shape[2], out=None,
                decoded_out=[stack[i] for i in enc],
                num_threads=self._decode_threads())
            from . import decode as _decode
            for k, i in enumerate(enc):
                if status[k] != native.DECODE_OK:
                    arr, labels[i] = self._fallback_record(recs[i])
                    entries[i] = ("arr", arr, labels[i])
                else:
                    _decode.STATS.count("fused_records")
                    if cache is not None and cache.admitting():
                        cache.insert(recs[i], stack[i].copy(), labels[i])
        for i in range(n):
            kind, payload = entries[i][0], entries[i][1]
            if kind == "arr":
                if payload.shape != shape or payload.dtype != np.uint8:
                    raise ValueError(
                        "device transform requires uniform uint8 records; "
                        "set transform_param { use_gpu_transform: false } "
                        "for this dataset")
                stack[i] = payload
        aug = compute_aug(self.tf, flats, shape[-2:], n)
        res = {self.top_names[0]: stack,
               aug_key(self.top_names[0]): aug}
        if len(self.top_names) > 1:
            res[self.top_names[1]] = np.asarray(labels, np.int32)
        return res

    def _raw_batch(self, raws: list[np.ndarray], flats: list[int]) -> dict:
        """Device-transform staging: uint8 stack + (B,3) aug decisions
        (same per-record Philox streams as the host transform)."""
        from .device_transform import aug_key, compute_aug
        first = raws[0]
        if first.dtype != np.uint8 or any(
                r.shape != first.shape or r.dtype != np.uint8 for r in raws):
            raise ValueError(
                "device transform requires uniform uint8 records; set "
                "transform_param { use_gpu_transform: false } for this "
                "dataset")
        aug = compute_aug(self.tf, flats, first.shape[-2:], len(raws))
        return {self.top_names[0]: np.stack(raws),
                aug_key(self.top_names[0]): aug}

    def _transform(self, raws: list[np.ndarray], flats: list[int]) -> np.ndarray:
        tf = self.tf
        if tf is None:
            # raws are host ndarrays from the dataset reader, never
            # device values; no RTT is paid here
            # host-sync: ok
            return np.stack([np.asarray(r, np.float32) for r in raws])
        if (self._native and raws[0].dtype == np.uint8
                and all(r.shape == raws[0].shape for r in raws)):
            from .. import native
            mean = tf.mean
            if mean is not None and mean.ndim == 3 and mean.shape[1] == 1:
                mean = mean.reshape(-1)  # per-channel (c,1,1) -> (c,)
                if mean.size == 1 and raws[0].shape[0] > 1:
                    # single mean_value broadcasts over channels
                    # (reference data_transformer.cpp); the C kernel
                    # indexes mean[ch], so repeat instead of letting it
                    # read past a 1-float buffer
                    mean = np.repeat(mean, raws[0].shape[0])
            return native.transform_batch(
                np.stack(raws), np.asarray(flats, np.int64),
                crop=tf.tp.crop_size, mean=mean, scale=tf.tp.scale,
                train=(tf.phase == "TRAIN"), mirror=tf.tp.mirror,
                seed=tf.seed or 0, num_threads=self.threads)
        # python reference path: per-record Philox RNG — deterministic
        # augmentation independent of thread scheduling
        return np.stack([tf(r, rng=tf.record_rng(f))
                         for r, f in zip(raws, flats)])

    # ------------------------------------------------------------------
    def _maybe_retune(self) -> None:
        """Reference data_layer.cpp:46-113 sizes parser/transformer thread
        counts once, at iteration 0, from free GPU memory and net cost.
        Here the analogue is the lookahead window (= number of batches
        built concurrently by the pool): need supply rate >= demand rate,
        i.e. lookahead >= build_time / step_time, re-measured at runtime
        and clamped by the host-RAM budget for in-flight batches."""
        with self._lock:
            builds = list(self._build_times)
            bytes_ = self._batch_bytes
        if len(builds) < 5 or len(self._gaps) < 5:
            return
        build = sorted(builds)[len(builds) // 2]
        gap = sorted(self._gaps)[len(self._gaps) // 2]
        want = math.ceil(build / max(gap, 1e-4)) + 1
        cap = _LOOKAHEAD_HARD_CAP
        if bytes_:
            cap = min(cap, max(int(self.mem_budget // bytes_) - 1, 1))
        want = min(max(want, 1), cap)
        if want != self.lookahead:
            log.info("prefetch auto-tune: lookahead %d -> %d "
                     "(build %.1f ms vs step %.1f ms, batch %.1f MiB, "
                     "budget %.0f MiB)", self.lookahead, want, build * 1e3,
                     gap * 1e3, bytes_ / 2**20,
                     self.mem_budget / 2**20)
            self.lookahead = want

    def __call__(self, it: int) -> dict:
        """feed_fn protocol: return the batch for micro-iteration `it`,
        scheduling lookahead batches in the background."""
        if self.auto:
            now = time.perf_counter()
            self._calls += 1
            if self._last_exit is not None and self._calls > 2:
                # skip the first couple of gaps — jit compilation noise
                self._gaps.append(now - self._last_exit)
            # first tune as soon as the warmup window fills, then
            # periodically (datasets and step times can change phase)
            if self._calls >= 8 and (self._calls == 8
                                     or self._calls % 64 == 0):
                self._maybe_retune()
        with self._lock:
            for ahead in range(it, it + self.lookahead + 1):
                if ahead not in self._futures:
                    self._futures[ahead] = self.pool.submit(self._build_batch,
                                                            ahead)
            fut = self._futures.pop(it)
            # drop stale entries (resume/seek) and, when a retune SHRANK
            # the window, best-effort cancel batches scheduled beyond it —
            # otherwise in-flight memory transiently exceeds mem_budget by
            # the old window size. Rebuild-on-demand is safe: batches are
            # pure functions of their index (_record_index + Philox). A
            # future that is already RUNNING can't be cancelled; it is
            # popped anyway (its memory frees when the build finishes) but
            # gets a done-callback so an exception it raises is logged
            # rather than silently swallowed with the dropped handle.
            for k in [k for k in self._futures
                      if k < it or k > it + self.lookahead]:
                dropped = self._futures.pop(k)
                if not dropped.cancel():
                    dropped.add_done_callback(self._log_abandoned)
        feeds = fut.result()
        if self.to_device is not None:
            feeds = self.to_device(feeds)
        if self.auto:
            self._last_exit = time.perf_counter()
        return feeds

    @staticmethod
    def _log_abandoned(fut) -> None:
        exc = None if fut.cancelled() else fut.exception()
        if exc is not None:
            log.warning("abandoned prefetch batch raised: %r", exc)

    def close(self) -> None:
        self.pool.shutdown(wait=False, cancel_futures=True)


class DeviceFeedQueue:
    """Double-buffered DEVICE-resident super-batch queue — the feed side
    of fused evaluation (solver._start_eval / _continue_eval).

    The host-side pipeline above (Feeder) overlaps batch ASSEMBLY with
    the train step but hands over one host batch at a time. This queue
    extends the lookahead to the device: `get(it0, k)` returns a stacked
    feeds pytree with leaves [k, 1, B, ...] already `device_put` (or
    mesh-sharded), and a single worker thread assembles + transfers the
    NEXT super-batch (the `hint`) while the current k-batch eval scan
    runs on the chip — so host->HBM transfer hides behind compute, the
    way the reference hides its NCCL allreduce behind backprop
    (parallel.cpp:166-169), but for the input stream. The unit axis is
    what the eval scan's body slices off (solver._build_eval_scan).

    Super-batches are pure functions of (it0, k) — the underlying
    feed_fn is indexed (Feeder's deterministic record striping) — so a
    mispredicted hint is dropped and rebuilt with no correctness cost.
    """

    def __init__(self, feed_fn, *, place=None):
        """place: optional callable(stacked_pytree) -> device pytree
        (e.g. MeshPlan.shard_feeds at batch_axis=2); default is a plain
        jax.device_put."""
        self.feed_fn = feed_fn
        self.place = place
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="device-feed")
        self._pending: dict[tuple[int, int], Future] = {}

    def _build(self, it0: int, k: int):
        import jax
        import jax.numpy as jnp
        micros = [self.feed_fn(m) for m in range(it0, it0 + k)]

        def stack(*leaves):
            if all(isinstance(x, np.ndarray) for x in leaves):
                arr = np.stack(leaves)  # one host copy, then one transfer
            else:
                # device-resident feeds (synthetic benches): stack on
                # device, never pulling them back to host
                arr = jnp.stack([jnp.asarray(x) for x in leaves])
            return arr.reshape((k, 1) + arr.shape[1:])

        tree = jax.tree.map(stack, *micros)
        if self.place is not None:
            return self.place(tree)
        return jax.device_put(tree)

    def prefetch(self, it0: int, k: int) -> None:
        """Schedule (it0, k) on the worker WITHOUT blocking — the
        test-boundary warmup path (solver._prefetch_test_feeds): the
        eval pass's first super-batch assembles and device_puts while
        the train step that ends at the boundary is still computing,
        so the boundary itself only pays the dispatch."""
        if (it0, k) not in self._pending:
            self._pending[(it0, k)] = self._pool.submit(self._build, it0, k)

    def ready(self, it0: int, k: int) -> bool:
        """True when (it0, k) is assembled and a get() would not block
        — the solver's opportunistic eval-chunk dispatch asks this
        between train steps. Schedules the build if it wasn't pending,
        so polling converges."""
        self.prefetch(it0, k)
        return self._pending[(it0, k)].done()

    def get(self, it0: int, k: int, hint: tuple[int, int] | None = None):
        """Super-batch for iterations [it0, it0+k); schedules `hint`
        (the next chunk's (it0, k)) on the worker before blocking."""
        fut = self._pending.pop((it0, k), None)
        if fut is None:
            fut = self._pool.submit(self._build, it0, k)
        if hint is not None and hint != (it0, k) and hint not in self._pending:
            self._pending[hint] = self._pool.submit(self._build, *hint)
        try:
            feeds = fut.result()
        except Exception as e:
            # name the chunk: the worker's traceback alone says nothing
            # about WHICH super-batch died, and a swallowed error here
            # used to leave the solver waiting on a future that would
            # never resolve usefully
            raise FeedError(
                f"feed super-batch for iterations [{it0}, {it0 + k}) "
                f"(it0={it0}, k={k}) failed to assemble: {e!r}") from e
        # drop stale prefetches (resume/seek or a schedule change): they
        # are pure functions of their indices, rebuild-on-demand is safe
        for key in [key for key in self._pending if key != hint]:
            dropped = self._pending.pop(key)
            if not dropped.cancel():
                dropped.add_done_callback(Feeder._log_abandoned)
        return feeds

    def close(self) -> None:
        for fut in self._pending.values():
            fut.cancel()
        self._pending.clear()
        self._pool.shutdown(wait=False, cancel_futures=True)


def feeder_from_layer(lp, phase: str, *, rank: int = 0, world: int = 1,
                      model_dir: str = "",
                      device_transform: bool = False,
                      solver_param=None) -> Feeder:
    """Build a Feeder from a Data/ImageData layer's prototxt config — the
    runner-side binding for DB-backed layers (reference
    DataLayer::LayerSetUp, data_layer.cpp:118-180). device_transform must
    be the consuming net's DataLayer.dev_transform. solver_param (when
    given) supplies run-level ingestion knobs: `decoded_cache_mb` > 0
    wraps the dataset in the bounded decoded-record cache tier
    (ISSUE 10, datasets.DecodedCacheDataset) unless the layer already
    opted into the whole-DB cache."""
    import os

    from .datasets import ImageFolderDataset, open_dataset

    tp = lp.transform_param
    tf = DataTransformer(tp, phase, model_dir=model_dir)
    tops = tuple(lp.top)
    cache_mb = float(getattr(solver_param, "decoded_cache_mb", 0.0) or 0.0)
    if cache_mb < 0:
        # loud, like every sibling knob (reduce_buckets/serve_* reject
        # negatives at init) — a typo'd budget must not silently
        # disable the cache
        raise ValueError(f"decoded_cache_mb must be >= 0, got {cache_mb}")
    if lp.type == "Data":
        p = lp.data_param
        ds = open_dataset(str(p.backend), os.path.join(model_dir, p.source))
        if p.cache:  # whole-DB RAM cache (reference data_param.cache)
            from .datasets import CachedDataset
            ds = CachedDataset(ds)
        elif cache_mb > 0:
            ds = DecodedCacheDataset(ds, cache_mb)
        shuffle = bool(p.shuffle) and phase == "TRAIN"
        # threads=0 (prototxt default) -> auto mode; prefetch seeds the
        # initial lookahead window (reference data_param.prefetch)
        return Feeder(ds, tf, p.batch_size, rank=rank, world=world,
                      shuffle=shuffle, top_names=tops,
                      threads=p.threads, lookahead=max(p.prefetch, 1),
                      device_transform=device_transform)
    if lp.type == "ImageData":
        p = lp.image_data_param
        ds = ImageFolderDataset(os.path.join(model_dir, p.source),
                                root=p.root_folder,
                                new_height=p.new_height, new_width=p.new_width,
                                is_color=p.is_color)
        return Feeder(ds, tf, p.batch_size, rank=rank, world=world,
                      shuffle=bool(p.shuffle) and phase == "TRAIN",
                      top_names=tops)
    raise ValueError(f"not a pipeline data layer: {lp.type}")


class ProbeShape(tuple):
    """Post-transform (C,H,W) that also remembers the raw record shape —
    the device-transform path needs both (the feed is the raw uint8
    record; the top blob is the transformed shape)."""

    raw: tuple | None = None

    def __new__(cls, shape, raw=None):
        self = super().__new__(cls, shape)
        self.raw = raw
        return self


def data_shape_probe(lp, model_dir: str = ""):
    """Open the dataset once to discover record shape, returning the
    post-transform (C,H,W) — the Net-side binding for Data layers
    (reference: DataLayer reads one sample in LayerSetUp). For uniform
    uint8 datasets the result carries `.raw`, enabling the in-graph
    transform path."""
    import os as _os

    from .datasets import open_dataset

    if lp.type == "Data":
        ds = open_dataset(str(lp.data_param.backend),
                          _os.path.join(model_dir, lp.data_param.source))
        img, _ = ds.get(0)
        tf = DataTransformer(lp.transform_param, "TEST", model_dir=model_dir)
        raw = tuple(img.shape) if img.dtype == np.uint8 else None
        if raw is not None:
            # the in-graph transform needs a uniform record shape; sample
            # records spread across the DB (a full scan would read the
            # whole dataset) — mixed-size layouts fall back to the host
            # path, which crops every record to a common shape
            n = len(ds)
            for i in {n // 2, n - 1, *range(1, min(n, 8))}:
                rec, _ = ds.get(int(i))
                if rec.shape != img.shape or rec.dtype != np.uint8:
                    raw = None
                    break
        return ProbeShape(tf.output_shape(img.shape), raw=raw)
    if lp.type == "HDF5Data":
        import h5py
        src = _os.path.join(model_dir, lp.hdf5_data_param.source)
        files = _h5_list_files(src)
        with h5py.File(files[0], "r") as h5:
            return [tuple(h5[top].shape[1:]) for top in lp.top]
    raise ValueError(f"no shape probe for layer type {lp.type}")


def _h5_list_files(source: str) -> list[str]:
    """Resolve an HDF5 source list: each line is a path, absolute or
    relative to the list file's directory (reference hdf5_data_layer.cpp)."""
    import os as _os
    base = _os.path.dirname(source)
    out = []
    with open(source) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(line if _os.path.isabs(line)
                           else _os.path.join(base, line))
    if not out:
        raise ValueError(f"{source}: empty HDF5 source list")
    return out


class HDF5Feeder:
    """Feeds batches from HDF5 files listed in a source file (reference
    hdf5_data_layer.cpp: datasets named by the layer's top blobs).

    STREAMING, file-at-a-time like the reference (LoadHDF5FileData loads
    one file, advances to the next when exhausted): peak RSS is bounded by
    the two largest files (a batch may straddle a boundary), never the
    whole dataset — an ImageNet-scale HDF5 source streams through. With
    shuffle, the file ORDER is re-drawn per epoch and rows are permuted
    per (epoch, file), mirroring the reference's file_permutation_ +
    data_permutation_ pair."""

    _CACHE_FILES = 2  # current + boundary-straddling neighbor

    def __init__(self, lp, *, model_dir: str = "", rank: int = 0,
                 world: int = 1, seed: int = 1701):
        import h5py
        import os as _os
        p = lp.hdf5_data_param
        self.batch = p.batch_size
        self.tops = list(lp.top)
        self.rank, self.world = rank, world
        self.shuffle = bool(p.shuffle)
        self.seed = seed
        self.files = _h5_list_files(_os.path.join(model_dir, p.source))
        # shape/dtype scan only — no data read until a batch needs it, but
        # every file must agree on tops, dtypes, and row shapes NOW (a
        # mismatch discovered mid-epoch would silently change the jitted
        # step's input dtype or KeyError long into training)
        self.lengths = []
        sig: dict[str, tuple] | None = None
        for path in self.files:
            with h5py.File(path, "r") as h5:
                missing = [t for t in self.tops if t not in h5]
                if missing:
                    raise ValueError(f"{path}: missing dataset(s) {missing}")
                this = {t: (h5[t].dtype, tuple(h5[t].shape[1:]))
                        for t in self.tops}
                if sig is None:
                    sig = this
                elif this != sig:
                    raise ValueError(
                        f"{path}: dtype/shape {this} differs from first "
                        f"file's {sig}")
                self.lengths.append(len(h5[self.tops[0]]))
        self.n = sum(self.lengths)
        self.lengths = np.asarray(self.lengths)
        self._sig = sig
        self._cache: dict[int, dict[str, np.ndarray]] = {}  # file -> arrays
        self._cache_order: list[int] = []
        # epoch layout (file order + cumulative bounds + row perms)
        # memoized for the CURRENT epoch only, like the reference's
        # file_permutation_/data_permutation_ pair
        self._layout_epoch = -1
        self._order: np.ndarray | None = None
        self._cum: np.ndarray | None = None
        self._row_perms: dict[int, np.ndarray] = {}

    # -- index plumbing ---------------------------------------------------
    def _epoch_layout(self, epoch: int):
        """(file order, cumulative end positions) for one epoch."""
        if epoch != self._layout_epoch:
            self._layout_epoch = epoch
            self._order = (np.random.RandomState(
                self.seed + epoch).permutation(len(self.files))
                if self.shuffle else np.arange(len(self.files)))
            self._cum = np.cumsum(self.lengths[self._order])
            self._row_perms = {}
        return self._order, self._cum

    def _row_perm(self, epoch: int, fi: int) -> np.ndarray:
        perm = self._row_perms.get(fi)
        if perm is None:
            perm = np.random.RandomState(
                (self.seed * 31 + epoch * 7919 + fi) % (2**32)).permutation(
                    int(self.lengths[fi]))
            self._row_perms[fi] = perm
        return perm

    def _file_arrays(self, fi: int) -> dict[str, np.ndarray]:
        arrays = self._cache.get(fi)
        if arrays is None:
            import h5py
            with h5py.File(self.files[fi], "r") as h5:
                # h5py datasets are host-side; this is the file read
                # itself, not a device materialization
                # host-sync: ok
                arrays = {t: np.asarray(h5[t]) for t in self.tops}
            self._cache[fi] = arrays
            self._cache_order.append(fi)
            while len(self._cache_order) > self._CACHE_FILES:
                self._cache.pop(self._cache_order.pop(0), None)
        return arrays

    def __call__(self, it: int) -> dict[str, np.ndarray]:
        flats = (it * self.batch * self.world + self.rank * self.batch
                 + np.arange(self.batch))
        epochs = flats // self.n
        within = flats % self.n
        # vectorized (epoch, within) -> (file, row): searchsorted over the
        # epoch's cumulative file bounds — O(batch log n_files), no
        # per-sample Python scan
        fis = np.empty(self.batch, np.int64)
        rows = np.empty(self.batch, np.int64)
        for ep in np.unique(epochs):
            m = epochs == ep
            order, cum = self._epoch_layout(int(ep))
            pos = np.searchsorted(cum, within[m], side="right")
            fi = order[pos]
            rows_in = within[m] - (cum[pos] - self.lengths[fi])
            if self.shuffle:
                for f in np.unique(fi):
                    fm = fi == f
                    rows_in[fm] = self._row_perm(int(ep), int(f))[rows_in[fm]]
            fis[m] = fi
            rows[m] = rows_in
        # one fancy-index COPY per spanned file (rows grouped by file):
        # no views pin evicted cache entries, so peak RSS really is
        # bounded by the cached files plus the batch itself
        out = {t: np.empty((self.batch, *self._sig[t][1]), self._sig[t][0])
               for t in self.tops}
        for fi in np.unique(fis):
            m = fis == fi
            arrays = self._file_arrays(int(fi))
            for t in self.tops:
                out[t][m] = arrays[t][rows[m]]
        return out

    def close(self) -> None:
        self._cache.clear()
        self._cache_order.clear()
