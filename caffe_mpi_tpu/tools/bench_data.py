"""bench_data — host data-pipeline throughput (img/s per backend) and
the ingestion stage breakdown (ISSUE 10).

The reference's pipeline perf story is DataReader/transformer thread
counts auto-tuned to keep GPUs fed (data_layer.cpp:46-113). Here the
host-side pipeline (dataset read -> decode -> transform -> batch) is the
part that must outrun the TPU step; this tool measures it in isolation,
per backend, with the same Feeder the training path uses.

The `ingest` section (default on; `--ingest-only` for just it) builds a
JPEG-encoded LMDB — the ImageNet-convert layout, where decode dominates
— and reports:
  * per-stage ms/batch: read (DB value fetch), crc (sidecar verify),
    decode (per-record, PIL and native), transform (native batch),
    assemble (stack + labels) — the evidence for WHERE host time goes;
  * end-to-end Feeder img/s for the PIL path (CAFFE_NATIVE_DECODE=0),
    the fused native path, and the decoded-record cache's post-warmup
    epoch — the A/B the acceptance criterion quotes;
All of it is CPU-only (no jax import); `--json` emits one JSON object.

Usage:
    python -m caffe_mpi_tpu.tools.bench_data [-n 4096] [-batch 256] \
        [-shape 3x256x256] [-backends lmdb,leveldb,datumfile,hdf5] \
        [--json] [--ingest-only] [--no-ingest] [--ingest-n N]

Prints one line per backend: img/s through Feeder + DataTransformer
(crop+mirror+mean-subtract — the AlexNet training transform), then the
ingest section.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np


def _make_records(n, shape, seed=0):
    rng = np.random.RandomState(seed)
    imgs = rng.randint(0, 256, (n, *shape), dtype=np.uint8)
    labels = rng.randint(0, 1000, n)
    return imgs, labels


def _write_db(backend, workdir, imgs, labels):
    """Serialize the synthetic dataset once per backend; returns the path
    (or HDF5 source-list path) the per-sweep feeders open."""
    from ..data.datasets import DatumFileDataset, encode_datum

    n = len(labels)
    recs = ((f"{i:08d}".encode(), encode_datum(imgs[i], int(labels[i])))
            for i in range(n))
    if backend == "lmdb":
        from ..data.lmdb_io import write_lmdb
        path = os.path.join(workdir, "b_lmdb")
        write_lmdb(path, recs)
    elif backend == "leveldb":
        from ..data.leveldb_io import write_leveldb
        path = os.path.join(workdir, "b_leveldb")
        write_leveldb(path, list(recs), compress=True)
    elif backend == "datumfile":
        path = os.path.join(workdir, "b.datumdb")
        DatumFileDataset.write(path, (r for _, r in recs))
    elif backend == "hdf5":
        import h5py
        h5 = os.path.join(workdir, "b.h5")
        with h5py.File(h5, "w") as f:
            f["data"] = imgs
            f["label"] = labels.astype(np.int64)
        path = os.path.join(workdir, "b_src.txt")
        with open(path, "w") as f:
            f.write(h5 + "\n")
    else:
        raise ValueError(backend)
    return path


def _feeder_for(backend, path, batch, crop, threads=0):
    from ..data import DataTransformer, Feeder
    from ..data.datasets import open_dataset
    from ..proto import TransformationParameter

    if backend == "hdf5":
        from ..data.feeder import HDF5Feeder
        from ..proto import NetParameter
        lp = NetParameter.from_text(
            'layer { name: "h" type: "HDF5Data" top: "data" top: "label"\n'
            f'  hdf5_data_param {{ source: "{path}" batch_size: {batch} '
            'shuffle: true } }').layer[0]
        return HDF5Feeder(lp)
    ds = open_dataset(backend.upper(), path)
    tp = TransformationParameter.from_text(
        f"crop_size: {crop} mirror: true mean_value: 104 "
        "mean_value: 117 mean_value: 123")
    return Feeder(ds, DataTransformer(tp, "TRAIN"), batch_size=batch,
                  shuffle=True, threads=threads)


def _ingest_feeder_img_s(path, batch, iters, crop, env_val, *,
                         decoded_cache_mb=0.0, epochs=1, n=0):
    """Per-worker batch-build rate over the encoded LMDB with the decode
    plane pinned to `env_val` ('' = auto/native, '0' = PIL). Batches are
    built DIRECTLY (`_build_batch_inner`), not through the prefetch
    queue — lookahead would build batches off the clock and flatter the
    number; the pool scales this per-worker rate by thread count at
    train time. With a decoded cache, `epochs=2` times only the SECOND
    epoch (the cached steady state). Returns (img/s, stats delta)."""
    from ..data import DataTransformer, Feeder
    from ..data import decode as dmod
    from ..data.datasets import DecodedCacheDataset, open_dataset
    from ..proto import TransformationParameter

    prev = os.environ.get("CAFFE_NATIVE_DECODE")
    if env_val:
        os.environ["CAFFE_NATIVE_DECODE"] = env_val
    else:
        os.environ.pop("CAFFE_NATIVE_DECODE", None)
    try:
        ds = open_dataset("LMDB", path)
        if decoded_cache_mb:
            ds = DecodedCacheDataset(ds, decoded_cache_mb)
        tp = TransformationParameter.from_text(
            f"crop_size: {crop} mirror: true mean_value: 104 "
            "mean_value: 117 mean_value: 123")
        # auto thread sizing: the fused native call threads the batch
        # decode internally (GIL released) with the pool width, which is
        # where it beats the per-record PIL loop — a PIL batch build is
        # sequential inside its worker no matter how many cores exist
        feeder = Feeder(ds, DataTransformer(tp, "TRAIN", seed=3),
                        batch_size=batch, shuffle=True, threads=0)
        it0 = 0
        if epochs > 1:  # warm the cache with a full first epoch
            for it in range(iters):
                feeder._build_batch_inner(it)
            it0 = iters
        feeder._build_batch_inner(it0)  # fused-path decision off-clock
        s0 = dmod.STATS.snapshot()
        t0 = time.perf_counter()
        for it in range(it0 + 1, it0 + iters):
            feeder._build_batch_inner(it)
        dt = time.perf_counter() - t0
        feeder.close()
        s1 = dmod.STATS.snapshot()
        stats = {k: s1[k] - s0[k] for k in s1}
        return batch * (iters - 1) / dt, stats
    finally:
        if prev is None:
            os.environ.pop("CAFFE_NATIVE_DECODE", None)
        else:
            os.environ["CAFFE_NATIVE_DECODE"] = prev


def _ingest_stage_breakdown(path, batch, iters, crop):
    """Direct per-stage instrumentation over the encoded LMDB: the same
    work the Feeder pipelines, timed stage-at-a-time so regressions have
    an address. Decode is timed on BOTH paths (per-record PIL and
    per-record native); transform is the native batch transformer (the
    production path for uniform uint8)."""
    from .. import native
    from ..data import decode as dmod
    from ..data.datasets import materialize_datum, parse_datum_fields
    from ..data.leveldb_io import crc32c
    from ..data.lmdb_io import LMDBReader, read_crc_sidecar

    reader = LMDBReader(path)
    keys = list(reader.keys())
    crcs = read_crc_sidecar(path, expect_count=len(keys))
    mean = np.asarray([104.0, 117.0, 123.0], np.float32)
    stages = {k: 0.0 for k in ("read", "crc", "decode_pil",
                               "decode_native", "transform", "assemble")}
    native_ok = native.available() and native.decode_available()
    for it in range(iters):
        idx = [(it * batch + i) % len(keys) for i in range(batch)]
        t0 = time.perf_counter()
        raws = [reader.get(keys[i]) for i in idx]
        stages["read"] += time.perf_counter() - t0
        if crcs is not None:
            t0 = time.perf_counter()
            for k, i in enumerate(idx):
                assert crc32c(raws[k]) == int(crcs[i])
            stages["crc"] += time.perf_counter() - t0
        fields = [parse_datum_fields(r) for r in raws]
        t0 = time.perf_counter()
        pil = [dmod._pil_decode(f.data) for f in fields]
        stages["decode_pil"] += time.perf_counter() - t0
        if native_ok:
            t0 = time.perf_counter()
            decoded = [native.decode_image_native(f.data) for f in fields]
            stages["decode_native"] += time.perf_counter() - t0
            decoded = [d if d is not None else p
                       for d, p in zip(decoded, pil)]
        else:
            decoded = pil
        # idx/labels are host ints from the DB read, never device values
        # host-sync: ok
        ids = np.asarray(idx, np.int64)
        t0 = time.perf_counter()
        if native_ok:
            out = native.transform_batch(
                np.stack(decoded), ids, crop=crop, mean=mean,
                scale=1.0, train=True, mirror=True, seed=3)
        else:
            out = np.stack([d[:, :crop, :crop].astype(np.float32)
                            for d in decoded]) - mean[:, None, None]
        stages["transform"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        # host-sync: ok
        labels = np.asarray([f.label for f in fields], np.int32)
        batch_out = {"data": np.ascontiguousarray(out), "label": labels}
        stages["assemble"] += time.perf_counter() - t0
        del batch_out
    return {k: round(v * 1e3 / iters, 2) for k, v in stages.items()}


def run_ingest(workdir, n, batch, shape, crop, codec="jpeg",
               cache_mb=512.0) -> dict:
    """Build the JPEG-encoded LMDB and produce the `ingest` block."""
    from .. import native
    from ..data.datasets import encode_datum_image
    from ..data.lmdb_io import write_lmdb

    imgs, labels = _make_records(n, shape, seed=11)
    path = os.path.join(workdir, "ingest_lmdb")
    t0 = time.perf_counter()
    write_lmdb(path, ((f"{i:08d}".encode(),
                       encode_datum_image(imgs[i], int(labels[i]), codec))
                      for i in range(n)))
    build_s = time.perf_counter() - t0
    iters = max(n // batch, 2)
    block = {
        "codec": codec, "n": n, "batch": batch,
        "shape": "x".join(map(str, shape)), "crop": crop,
        "db_build_s": round(build_s, 1),
        "native_available": bool(native.available()
                                 and native.decode_available()),
        "stages_ms_per_batch": _ingest_stage_breakdown(
            path, batch, iters, crop),
    }
    pil_img_s, _ = _ingest_feeder_img_s(path, batch, iters, crop, "0")
    nat_img_s, nat_stats = _ingest_feeder_img_s(path, batch, iters, crop,
                                                "")
    block["pil_img_s"] = round(pil_img_s, 0)
    block["native_img_s"] = round(nat_img_s, 0)
    block["native_speedup"] = round(nat_img_s / max(pil_img_s, 1e-9), 2)
    block["fused_batches"] = nat_stats["fused_batches"]
    block["fused_records"] = nat_stats["fused_records"]
    cached_img_s, cache_stats = _ingest_feeder_img_s(
        path, batch, iters, crop, "", decoded_cache_mb=cache_mb, epochs=2)
    block["cached_img_s"] = round(cached_img_s, 0)
    block["cache_epoch2_decodes"] = cache_stats["decode_calls"]
    return block


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_data")
    p.add_argument("-n", "--n", type=int, default=4096)
    p.add_argument("-batch", "--batch", type=int, default=256)
    p.add_argument("-shape", "--shape", default="3x256x256")
    p.add_argument("-crop", "--crop", type=int, default=227)
    p.add_argument("-backends", "--backends",
                   default="lmdb,leveldb,datumfile,hdf5")
    p.add_argument("-device-transform", "--device-transform",
                   action="store_true",
                   help="stage raw uint8 + aug decisions (the in-graph "
                   "transform feed path) instead of transforming on host")
    p.add_argument("-threads", "--threads", default="0",
                   help="comma list of Feeder thread counts to sweep "
                   "(0 = auto mode, the prototxt default) — shows "
                   "multi-core scaling of the host pipeline")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON object instead of text lines")
    p.add_argument("--ingest-only", action="store_true",
                   help="skip the classic backend sweep; just the "
                   "encoded-LMDB ingest section")
    p.add_argument("--no-ingest", action="store_true",
                   help="classic backend sweep only")
    p.add_argument("--ingest-n", type=int, default=0,
                   help="records in the encoded ingest DB (0 = "
                   "min(n, 1024))")
    args = p.parse_args(argv)
    shape = tuple(int(x) for x in args.shape.split("x"))
    sweeps = [int(t) for t in args.threads.split(",")]
    doc: dict = {"backends": []}

    if not args.ingest_only:
        # the classic sweep's dataset (~800 MB at the defaults) — the
        # ingest section builds its own, so skip it under --ingest-only
        imgs, labels = _make_records(args.n, shape)
    iters = max(args.n // args.batch, 1)
    mode = "raw+aug staging" if args.device_transform else "host transform"
    with tempfile.TemporaryDirectory() as workdir:
        for backend in (args.backends.split(",")
                        if not args.ingest_only else []):
            t_build = time.perf_counter()
            path = _write_db(backend, workdir, imgs, labels)
            build_s = time.perf_counter() - t_build
            # HDF5Feeder has no thread pool — the sweep would print
            # identical single-threaded runs under misleading labels
            backend_sweeps = [None] if backend == "hdf5" else sweeps
            for threads in backend_sweeps:
                feeder = _feeder_for(backend, path, args.batch, args.crop,
                                     threads or 0)
                if args.device_transform:
                    if not hasattr(feeder, "device_transform"):
                        print(f"{backend:>10}: n/a "
                              "(no device-transform path)")
                        close = getattr(feeder, "close", None)
                        if close:
                            close()
                        break
                    feeder.device_transform = True
                feeder(0)  # warm caches / thread pools
                t0 = time.perf_counter()
                for it in range(1, iters + 1):
                    feeder(it)
                dt = time.perf_counter() - t0
                close = getattr(feeder, "close", None)
                if close:
                    close()
                tdesc = ("threads n/a" if threads is None
                         else "auto" if threads == 0 else f"t={threads}")
                img_s = args.batch * iters / dt
                doc["backends"].append(
                    {"backend": backend, "mode": mode, "threads": tdesc,
                     "img_s": round(img_s, 0)})
                if not args.json:
                    print(f"{backend:>10}: {img_s:8.0f} img/s "
                          f"({args.batch}x{args.shape}, crop {args.crop}, "
                          f"{mode}, {tdesc}, build {build_s:.1f}s)")
        if not args.no_ingest:
            # ingestion section (ISSUE 10): JPEG-encoded LMDB, stage
            # breakdown + PIL-vs-native-fused A/B + cached epoch
            n_ing = args.ingest_n or min(args.n, 1024)
            ing = run_ingest(workdir, n_ing, min(args.batch, n_ing),
                             shape, args.crop)
            doc["ingest"] = ing
            if not args.json:
                st = ing["stages_ms_per_batch"]
                print(f"    ingest: JPEG LMDB n={ing['n']} "
                      f"b={ing['batch']} crop={ing['crop']} — "
                      "ms/batch: "
                      + " ".join(f"{k}={v}" for k, v in st.items()))
                print(f"    ingest: PIL {ing['pil_img_s']:.0f} img/s | "
                      f"native fused {ing['native_img_s']:.0f} img/s "
                      f"({ing['native_speedup']}x) | decoded-cache "
                      f"epoch2 {ing['cached_img_s']:.0f} img/s "
                      f"({ing['cache_epoch2_decodes']} decodes)")
    if args.json:
        print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
