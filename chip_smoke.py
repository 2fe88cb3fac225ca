#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls —
`caffe_mpi_tpu.tools.cli` -> Solver -> one jitted step, and `caffe serve`
-> ServingEngine over real HTTP — at the full width of the headline
model (models/alexnet, batch 256 x 3 x 227 x 227, random weights from
the recipe's seed, a few tens of iterations), and runs every Pallas
kernel through Mosaic against its jnp reference.

Legs, each its own process (a chip belongs to one process at a time; the
parent here never imports jax):

  train-f32       caffe train, one chip, default flags
  train-bf16      caffe train -precision bf16 (Pallas LRN on by default)
  serve           caffe serve -smoke 32 -require_native_ingest
  kernels         LRN / flash / ring-flash vs their references
  train-dp4-f32   caffe train -gpu all           (needs >= 4 devices)
  train-dp4-bf16  caffe train -gpu all -precision bf16

Every leg first requires `jax.devices()[0].platform == "tpu"`; anything
else is a non-zero exit that names what it found, so under
JAX_PLATFORMS=cpu this script fails within seconds, saying "cpu". With
fewer than four devices the dp4 legs are reported as not run (a dp4 leg
asked for by name fails and names the count). Any failed leg makes the
run exit non-zero and no result line is printed.

Writes only to chiprun_out/chip_smoke/ (per-leg logs + summary.json) and
to temp dirs for snapshots. The compile cache follows
caffe_mpi_tpu/utils/compile_cache.py. The last stdout line of a passing
run is {"ok": true, "device": {"platform", "kind", "count"}}.

    python chip_smoke.py              # everything the visible devices allow
    python chip_smoke.py --leg NAME   # one leg, in this process
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import io
import json
import logging
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
RESULT_TAG = "CHIP_SMOKE_RESULT "
EXIT_WRONG_PLATFORM = 3

# the whole run must end inside 1200 s, compilation included
TOTAL_BUDGET_S = 1140
LEG_DEADLINE_S = 420

DP_DEVICES = 4   # what the `-gpu all` legs need to see


class SmokeFailure(AssertionError):
    """A leg's check did not hold."""


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# train legs
# ---------------------------------------------------------------------------

def _param_probes(params) -> dict:
    """Host copies of the smallest parameter of the first and of the
    last learnable layer — enough to see that an update reached both
    ends of the net without pulling 240 MB off the device."""
    import numpy as np
    names = [ln for ln in params if params[ln]]
    probes = {}
    for ln in (names[0], names[-1]):
        pn = min(params[ln], key=lambda p: params[ln][p].size)
        probes[f"{ln}/{pn}"] = np.asarray(params[ln][pn], np.float32).copy()
    return probes


def leg_train(*, precision: str = "", gpu_all: bool = False,
              solver: str = "models/alexnet/solver.prototxt",
              max_iter: int = 40, test_iter: int = 2,
              classes: int | None = 1000, on_chip: bool = True) -> dict:
    """`caffe train` through cli.main in this process, then the checks
    on what it left behind. `on_chip=False` (the CPU rehearsal) skips
    only what a CPU cannot show: Mosaic calls and device memory."""
    import jax
    import numpy as np

    import caffe_mpi_tpu.solver as solver_pkg
    from caffe_mpi_tpu.parallel import MeshPlan, reduction
    from caffe_mpi_tpu.tools import cli
    from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache

    n_dev = len(jax.devices())
    require(not gpu_all or n_dev >= DP_DEVICES,
            f"this leg needs >= {DP_DEVICES} devices, jax found {n_dev}")
    require("CAFFE_LRN_PALLAS" not in os.environ,
            "CAFFE_LRN_PALLAS is set: the smoke runs the shipped routing")
    cache_dir = enable_compile_cache()
    cache_before = _cache_entries(cache_dir)

    solvers, probes0, feed_spans, displays = [], {}, set(), []

    class RecordingSolver(solver_pkg.Solver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            solvers.append(self)
            probes0.update(_param_probes(self.params))

    real_solver, shard_feeds = solver_pkg.Solver, MeshPlan.shard_feeds

    def recording_shard_feeds(self, feeds, batch_axis=0):
        out = shard_feeds(self, feeds, batch_axis=batch_axis)
        for x in jax.tree.leaves(out):
            feed_spans.add((len(x.sharding.device_set),
                            x.sharding.is_fully_replicated))
        return out

    class DisplayTap(logging.Handler):
        def emit(self, record):
            if str(record.msg).startswith("Iteration %d"):
                displays.append((int(record.args[0]),
                                 float(record.args[3])))

    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    argv = ["train", "-solver", solver, "-synthetic",
            "-max_iter", str(max_iter), "-test_iter", str(test_iter),
            "-snapshot_prefix", os.path.join(tmp, "snap")]
    if precision:
        argv += ["-precision", precision]
    if gpu_all:
        argv += ["-gpu", "all"]
    tap = DisplayTap()
    slog = logging.getLogger("caffe_mpi_tpu.solver")
    slog.addHandler(tap)
    solver_pkg.Solver = RecordingSolver
    MeshPlan.shard_feeds = recording_shard_feeds
    t0 = time.monotonic()
    try:
        rc = cli.main(argv)
        wall = time.monotonic() - t0
        require(rc == 0, f"caffe {' '.join(argv)} returned {rc}")
        require(len(solvers) == 1, f"{len(solvers)} Solvers were built")
        s = solvers[0]
        require(s.iter == max_iter, f"stopped at iter {s.iter}/{max_iter}")

        require(len(displays) >= 2, f"only {len(displays)} display lines")
        require(all(math.isfinite(l) for _, l in displays),
                f"non-finite loss at a display: {displays}")
        if classes:
            # random weights: the first loss is the uniform-softmax loss
            want = math.log(classes)
            require(abs(displays[0][1] - want) < 0.05 * want,
                    f"first loss {displays[0][1]:.4f}, expected about "
                    f"ln({classes}) = {want:.4f} from random weights")
        require(s.test_pass_count >= 1, "no test pass ran")

        moved = {}
        for key, before in probes0.items():
            ln, pn = key.split("/")
            after = np.asarray(s.params[ln][pn], np.float32)
            require(np.all(np.isfinite(after)), f"{key} is not finite")
            moved[key] = float(np.max(np.abs(after - before)))
        require(all(v > 0 for v in moved.values()),
                f"parameters did not change: {moved}")
        snaps = glob.glob(os.path.join(tmp, f"snap_iter_{max_iter}.*"))
        require(snaps, f"no final snapshot under {tmp}")

        result = {"displays": displays, "param_max_abs_change": moved,
                  "wall_s_observed_in_smoke": round(wall, 1),
                  "devices_used": n_dev if gpu_all else 1}
        if precision == "bf16" or gpu_all:
            # the step's compiled text (one more compile; the plain f32
            # one-chip step has nothing to look for in it)
            text = s.step_hlo_text(cli._synthetic_feed(s.net))
            result["mosaic_calls"] = text.count("tpu_custom_call")
            result.update(reduction.collective_stats(text))
        if gpu_all:
            require(feed_spans == {(n_dev, False)},
                    f"feeds not batch-sharded over {n_dev} devices: "
                    f"(devices, replicated) = {sorted(feed_spans)}")
            require(result["all_reduces"] >= 1,
                    "the compiled data-parallel step has no all-reduce")
        if on_chip:
            if precision == "bf16":
                # AlexNet norm1 + norm2, forward + backward
                require(result["mosaic_calls"] >= 4,
                        f"bf16 step holds {result['mosaic_calls']} "
                        f"tpu_custom_call(s): the Pallas LRN kernels did "
                        f"not run compiled")
            used = jax.devices() if gpu_all else jax.devices()[:1]
            peaks = [d.memory_stats()["peak_bytes_in_use"] for d in used]
            require(all(p > 0 for p in peaks),
                    f"a device never held memory: peaks {peaks}")
            result["peak_bytes_in_use"] = peaks
        result["compile_cache"] = {
            "dir": cache_dir, "entries_before": cache_before,
            "entries_after": _cache_entries(cache_dir)}
        return result
    finally:
        MeshPlan.shard_feeds = shard_feeds
        solver_pkg.Solver = real_solver
        slog.removeHandler(tap)
        shutil.rmtree(tmp, ignore_errors=True)


def _cache_entries(cache_dir: str) -> int | None:
    if not cache_dir:
        return None
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------

def leg_serve(*, model: str = "models/alexnet/deploy.prototxt",
              requests: int = 32) -> dict:
    """`caffe serve -smoke N` (AOT bucket warm, requests over real HTTP,
    zero post-warm-up compiles, native ingest), then the serving
    engine's rows against the plain padded forward of the same net."""
    import jax
    import numpy as np

    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    from caffe_mpi_tpu.serving import ServingEngine
    from caffe_mpi_tpu.serving.engine import BucketedForward
    from caffe_mpi_tpu.tools import cli

    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        # serve a copy: the engine journals next to the deploy prototxt
        deploy = os.path.join(tmp, "deploy.prototxt")
        shutil.copy(os.path.join(ROOT, model), deploy)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.main(["serve", "-model", deploy, "-smoke",
                           str(requests), "-require_native_ingest"])
        sys.stdout.write(out.getvalue())
        require(rc == 0, f"caffe serve -smoke returned {rc}")
        line = next(l for l in out.getvalue().splitlines()
                    if l.startswith('{"serve_smoke"'))
        stats = json.loads(line)["serve_smoke"]
        require(stats["post_warmup_compiles"] == 0,
                f"{stats['post_warmup_compiles']} post-warm-up compiles")
        require(stats["compile_count"] == stats["warmed_buckets"] > 0,
                f"compile_count {stats['compile_count']} != warmed "
                f"buckets {stats['warmed_buckets']}")
        require(stats["native_ingest_engaged"], "native ingest idle")
        require(stats["requests"] >= requests,
                f"{stats['requests']} of {requests} requests served")

        with ServingEngine() as engine:
            engine.load_model("m", deploy)
            served = engine.model("m")
            rng = np.random.RandomState(0)
            _, c, h, w = served.fwd.input_shape()
            imgs = [rng.rand(h, w, c).astype(np.float32) for _ in range(3)]
            got = engine.classify("m", imgs)
            # reference: the classic loop — rows padded to the deploy
            # net's declared batch through one plain jitted Net.apply
            param = NetParameter.from_file(deploy)
            net = Net(param, phase="TEST")
            rows = np.stack([served.preprocess(im) for im in imgs])
            padded = BucketedForward.pad(rows, served.fwd.ladder[-1])
            params, state = served.ensure_resident()
            blob = served.fwd.out_blob()
            ref = np.asarray(jax.jit(
                lambda p, s, x: net.apply(
                    p, s, {served.fwd.input_blob(): x},
                    train=False)[0][blob])(params, state, padded))[:3]
        require(got.shape == ref.shape == (3, got.shape[1]),
                f"score shapes {got.shape} vs {ref.shape}")
        require(np.all(np.isfinite(got)), "non-finite scores")
        # two XLA programs (bucket 4 vs the declared batch) whose f32
        # convolutions round through the MXU's bf16 passes in different
        # tile orders: agreement is at that level, not bitwise
        err = _rel_err(got, ref)
        require(err < 2e-2, f"served rows differ from the plain forward "
                            f"by {err:.2e} of the score scale")
        return {"ladder": list(served.fwd.ladder),
                "compile_count": stats["compile_count"],
                "post_warmup_compiles": stats["post_warmup_compiles"],
                "native_ingest_engaged": stats["native_ingest_engaged"],
                "rows_vs_plain_forward_rel_err": err}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# kernels leg
# ---------------------------------------------------------------------------

def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _check_kernel(name, kernel_fn, ref_fn, args, tol, on_chip) -> dict:
    """Compile `kernel_fn` (forward + gradient), require Mosaic in the
    compiled text, run it, and compare every output with `ref_fn`'s at
    `tol` of the output's own scale."""
    import jax
    compiled = jax.jit(kernel_fn).lower(*args).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    if on_chip:
        require(calls >= 1, f"{name}: no tpu_custom_call in the compiled "
                            f"text — the kernel did not go through Mosaic")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*args)
    got = compiled(*args)
    errs = [_rel_err(g, w) for g, w in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    require(all(math.isfinite(e) and e < tol for e in errs),
            f"{name}: differs from its reference by {errs} of the output "
            f"scale (tolerance {tol})")
    return {"mosaic_calls": calls, "rel_err": [float(f"{e:.2e}")
                                               for e in errs], "tol": tol}


def leg_kernels(*, on_chip: bool = True, scale: int = 1) -> dict:
    """Each Pallas entry against its lax/jnp reference. `scale` shrinks
    the shapes for the CPU rehearsal (interpreter mode is slow).

    Tolerances are fractions of each output's max magnitude:
    - LRN bf16, 1e-2: in-kernel math is f32, the output rounds once to
      bf16 (half an ulp = 2^-9 relative); the reference rounds the same
      way from XLA's own pow.
    - flash f32, 2e-2 forward-only / 3e-2 with gradients: the reference
      runs at `highest` matmul precision while the kernel's f32 dots
      round through the MXU's bf16 passes (2^-8 per product), summed
      over tiles in a different order (first chip run, PR 21: 3e-3 to
      5e-3 observed).
    - flash bf16 inputs, 5e-2: as above plus bf16 I/O rounding.
    - dropless experts bf16, 5e-2: three bf16 products in a row and a
      bf16 intermediate against an f32 loop over the same bf16 weights.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from caffe_mpi_tpu.ops.attention import (attention,
                                             sequence_parallel_attention)
    from caffe_mpi_tpu.ops.lrn import lrn_across_channels

    rng = np.random.RandomState(0)
    results = {}

    def lrn_ref(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
        x32 = x.astype(jnp.float32)
        half = (size - 1) // 2
        ws = lax.reduce_window(
            jnp.square(x32), 0.0, lax.add, (1, size, 1, 1), (1, 1, 1, 1),
            ((0, 0), (half, half), (0, 0), (0, 0)))
        return (x32 * jnp.power(k + ws * (alpha / size), -beta)
                ).astype(x.dtype)

    def with_grad(f):
        def g(*args):
            out, vjp = jax.vjp(f, *args)
            return out, vjp(jnp.ones_like(out))
        return g

    # both operand views of ops/lrn.py: spatial on the lanes (any batch
    # under 128; the rehearsal shrinks the batch) and batch on the lanes
    # (a multiple of 128; the rehearsal shrinks the image instead)
    n = max(32 // scale, 2)
    for tag, c, hw in (("norm1", 96, 55), ("norm2", 256, 27)):
        for shape in ((n, c, hw, hw), (128, c, hw // scale, hw // scale)):
            x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 2,
                            jnp.bfloat16)
            results[f"lrn-alexnet-{tag}-b{shape[0]}-bf16"] = _check_kernel(
                f"lrn {tag} batch {shape[0]}",
                with_grad(lambda x: lrn_across_channels(
                    x, 5, 1e-4, 0.75, 1.0)),
                with_grad(lrn_ref), (x,), 1e-2, on_chip)

    def qkv(b, s, h, d, dtype):
        return tuple(jnp.asarray(rng.randn(b, s, h, d).astype(np.float32),
                                 dtype) for _ in range(3))

    def flash(causal):
        return lambda q, k, v: attention(q, k, v, causal=causal,
                                         use_flash=True)

    def plain(causal):
        # the jnp path in f32 whatever the I/O dtype: a bf16 softmax
        # would be a worse reference than the kernel under test
        def f(q, k, v):
            q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
            return attention(q32, k32, v32, causal=causal).astype(q.dtype)
        return f

    results["flash-causal-s64-d32-f32"] = _check_kernel(
        "flash causal S=64 d=32 (models/transformer_lm)",
        with_grad(flash(True)), with_grad(plain(True)),
        qkv(8, 64, 4, 32, jnp.float32), 3e-2, on_chip)
    s_mid = 1024 // scale
    results[f"flash-causal-s{s_mid}-d128-bf16"] = _check_kernel(
        f"flash causal S={s_mid} d=128 bf16",
        with_grad(flash(True)), with_grad(plain(True)),
        qkv(2, s_mid, 4, 128, jnp.bfloat16), 5e-2, on_chip)
    s_long = 8192 // scale
    results[f"flash-fwd-s{s_long}-d128-f32"] = _check_kernel(
        f"flash forward S={s_long} d=128 f32",
        flash(False), plain(False),
        qkv(1, s_long, 2, 128, jnp.float32), 2e-2, on_chip)

    # the published-width shapes of the benchmark's language-model cell
    # (models/smallthinker_21b_a3b): 28 query heads over 4 key/value heads
    # of 128 with a window, and the dropless expert layer (6 of 64 experts
    # a token, 16 held, gated ReLU experts 2560 -> 768 -> 2560). The
    # attention at S 2048 and at the cell's own S 8192 with its 4096 window,
    # where every run of the kernels' tile walk (window edge, inside,
    # diagonal) holds tiles; its jnp reference goes one key/value head at a
    # time, recomputed in its backward pass: 28 heads of 8192 x 8192
    # float32 scores do not fit the chip at once
    def windowed(flash, window, block=0):
        # block: the block-diffusion mask in the band's place
        mask = dict(block_diffusion=block) if block \
            else dict(causal=True, window=window)

        def f(q, k, v):
            if flash:
                return attention(q, k, v, use_flash=True,
                                 **mask).astype(jnp.bfloat16)

            @jax.checkpoint
            def group(qkv):   # (a group's query heads, 1 key/value head)
                qg, kg, vg = (t.astype(jnp.float32)[None] for t in qkv)
                return attention(qg.transpose(0, 2, 1, 3), kg[:, :, None],
                                 vg[:, :, None], **mask)[0]
            b, s, h, d = q.shape
            hkv = k.shape[2]
            out = jax.lax.map(group, (
                q[0].reshape(s, hkv, h // hkv, d).transpose(1, 2, 0, 3),
                k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)))
            return out.transpose(1, 0, 2, 3).reshape(
                b, s, h, v.shape[-1]).astype(jnp.bfloat16)
        return f
    for s_win in (2048 // scale, 8192 // scale):
        q = jnp.asarray(rng.randn(1, s_win, 28, 128).astype(np.float32),
                        jnp.bfloat16)
        kv = tuple(jnp.asarray(rng.randn(1, s_win, 4, 128).astype(
            np.float32), jnp.bfloat16) for _ in range(2))
        window = s_win // 2
        results[f"flash-window{window}-28over4-s{s_win}-d128-bf16"] = \
            _check_kernel(
                f"flash window {window} 28/4 heads S={s_win} bf16",
                with_grad(windowed(True, window)),
                with_grad(windowed(False, window)), (q, *kv), 5e-2, on_chip)

    # latent attention's shape in the benchmark's second language-model
    # cell (models/joyai_llm_flash): 32 heads, queries and keys 192 wide
    # (128 + 64 rotary lanes), values 128 wide, S 8192, no window
    s_mla = 8192 // scale
    q, k = (jnp.asarray(rng.randn(1, s_mla, 32, 192).astype(np.float32),
                        jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(rng.randn(1, s_mla, 32, 128).astype(np.float32),
                    jnp.bfloat16)
    results[f"flash-latent-32heads-s{s_mla}-d192-dv128-bf16"] = \
        _check_kernel(f"flash causal 32 heads S={s_mla} d=192 dv=128 bf16",
                      with_grad(windowed(True, 0)),
                      with_grad(windowed(False, 0)), (q, k, v), 5e-2,
                      on_chip)

    # the block-diffusion cell's heads (models/sdar_30b_a3b): 32 query heads
    # over 4 key/value heads of 128 under the block mask, blocks of 4, over
    # a [noisy | clean] sequence of 2 x 4,096 rows (half the cell's: 8 heads
    # of 16,384 x 16,384 float32 scores do not fit beside the rest)
    s_bd = 8192 // scale
    q = jnp.asarray(rng.randn(1, s_bd, 32, 128).astype(np.float32),
                    jnp.bfloat16)
    kv = tuple(jnp.asarray(rng.randn(1, s_bd, 4, 128).astype(np.float32),
                           jnp.bfloat16) for _ in range(2))
    results[f"flash-blockdiffusion4-32over4-s{s_bd}-d128-bf16"] = \
        _check_kernel(f"flash block diffusion 4, 32/4 heads S={s_bd} bf16",
                      with_grad(windowed(True, 0, block=4)),
                      with_grad(windowed(False, 0, block=4)), (q, *kv), 5e-2,
                      on_chip)

    from caffe_mpi_tpu.ops.moe import moe_dropless
    d_model, width, held = 2560 // scale, 768 // scale, 16
    tokens = 2048 // scale
    moe_params = {
        "gate": jnp.asarray(rng.randn(d_model, 64) * 0.02, jnp.float32),
        **{name: jnp.asarray(rng.randn(*shape) * 0.02, jnp.float32)
           for name, shape in (("w1", (held, d_model, width)),
                               ("w3", (held, d_model, width)),
                               ("w2", (held, width, d_model)))}}
    x_moe = jnp.asarray(rng.randn(tokens, d_model), jnp.float32)

    def experts_sorted(params, x):
        cast = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        xb = x.astype(jnp.bfloat16)
        y, rows = moe_dropless(cast, xb, xb, top_k=6, first_expert=16)
        return y.astype(jnp.float32), rows

    def experts_looped(params, x):
        # the same choices (bf16 router product), then each held expert
        # over every token in f32, masked
        xb = x.astype(jnp.bfloat16)
        logits = jnp.dot(xb, params["gate"].astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        top, ids = lax.top_k(logits, 6)
        w = jax.nn.softmax(top, axis=-1)
        x32 = xb.astype(jnp.float32)
        f32 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        y = jnp.zeros_like(x32)
        rows = []
        with jax.default_matmul_precision("highest"):
            for e in range(held):
                chosen = ids == 16 + e
                w_e = jnp.sum(jnp.where(chosen, w, 0.0), -1)
                out = (jax.nn.relu(x32 @ f32(params["w1"][e]))
                       * (x32 @ f32(params["w3"][e]))) @ f32(params["w2"][e])
                y = y + w_e[:, None] * out
                rows.append(jnp.sum(chosen).astype(jnp.float32))
        return y, jnp.stack(rows)
    results[f"moe-dropless-6of64-held16-t{tokens}-bf16"] = _check_kernel(
        f"dropless experts, {tokens} tokens, 16 of 64 held",
        with_grad(lambda p, x: experts_sorted(p, x)[0]),
        with_grad(lambda p, x: experts_looped(p, x)[0]),
        (moe_params, x_moe), 5e-2, on_chip)

    n_dev = len(jax.devices())
    if n_dev >= 4:
        from caffe_mpi_tpu.parallel import MeshPlan
        mesh = MeshPlan.from_shape(1, 4, devices=jax.devices()[:4]).mesh
        s_ring = 1024 // scale
        results["ring-flash-causal-4chip-f32"] = _check_kernel(
            "ring-flash under shard_map on 4 chips",
            with_grad(lambda q, k, v: sequence_parallel_attention(
                q, k, v, mesh, seq_axis="model", causal=True,
                use_flash=True)),
            with_grad(plain(True)),
            qkv(2, s_ring, 4, 64, jnp.float32), 3e-2, on_chip)
    else:
        results["ring-flash-causal-4chip-f32"] = {
            "not_run": f"needs 4 devices, jax found {n_dev}"}
    return results


# ---------------------------------------------------------------------------
# leg registry + child entry
# ---------------------------------------------------------------------------

LEGS = {
    "train-f32": lambda: leg_train(),
    "train-bf16": lambda: leg_train(precision="bf16"),
    "serve": leg_serve,
    "kernels": leg_kernels,
    "train-dp4-f32": lambda: leg_train(gpu_all=True),
    "train-dp4-bf16": lambda: leg_train(precision="bf16", gpu_all=True),
}


def run_leg(name: str) -> int:
    """Child entry: this process owns the chip for one leg."""
    os.chdir(ROOT)  # the recipes name their nets relative to the checkout
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[{name}] jax {jax.__version__} device: {json.dumps(device)}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: leg {name} needs platform 'tpu'; jax found "
              f"'{dev.platform}' ({dev.device_kind} x {device['count']})",
              file=sys.stderr)
        return EXIT_WRONG_PLATFORM
    try:
        result = LEGS[name]()
    except SmokeFailure as e:
        print(f"chip_smoke: leg {name} FAILED: {e}", file=sys.stderr)
        return 1
    print(RESULT_TAG + json.dumps(
        {"leg": name, "ok": True, "device": device, "result": result}),
        flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: builds, launches the legs one after another, never imports jax
# ---------------------------------------------------------------------------

def build_native() -> None:
    """The .so is not committed: build it from the committed sources,
    every time, and fail if the build fails."""
    r = subprocess.run(
        ["sh", os.path.join(ROOT, "caffe_mpi_tpu", "native", "build.sh")],
        capture_output=True, text=True, timeout=300)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise SystemExit(f"chip_smoke: native build failed "
                         f"(rc {r.returncode})")


def run_all() -> int:
    from caffe_mpi_tpu.utils.subproc import run_contained
    started = time.monotonic()
    os.makedirs(OUT_DIR, exist_ok=True)
    build_native()

    summary, device, failed = {}, None, []
    for name in LEGS:   # in run order; the first passing leg names the device
        seen = device["count"] if device else 0
        if "-dp4-" in name and seen < DP_DEVICES:
            summary[name] = {"not_run": f"needs {DP_DEVICES} devices, "
                                        f"jax found {seen}"}
            print(f"[{name}] not run: {summary[name]['not_run']}",
                  flush=True)
            continue
        left = TOTAL_BUDGET_S - (time.monotonic() - started)
        if left < 30:
            failed.append(name)
            summary[name] = {"ok": False, "error": "run budget exhausted"}
            continue
        t0 = time.monotonic()
        rc, out, err = run_contained(
            [sys.executable, os.path.abspath(__file__), "--leg", name],
            min(LEG_DEADLINE_S, left), cwd=ROOT)
        wall = round(time.monotonic() - t0, 1)
        with open(os.path.join(OUT_DIR, f"{name}.log"), "w") as f:
            f.write(out + "\n--- stderr ---\n" + err)
        record = next((json.loads(l[len(RESULT_TAG):])
                       for l in reversed(out.splitlines())
                       if l.startswith(RESULT_TAG)), None)
        if rc == 0 and record is not None:
            device = device or record["device"]
            summary[name] = dict(record, wall_s=wall)
            print(f"[{name}] ok in {wall}s on {record['device']}",
                  flush=True)
            continue
        failed.append(name)
        tail = [l for l in err.strip().splitlines() if l.strip()][-15:]
        why = "deadline expired" if rc is None else f"rc {rc}"
        summary[name] = {"ok": False, "error": why, "stderr_tail": tail}
        print(f"[{name}] FAILED ({why}) after {wall}s:\n  "
              + "\n  ".join(tail), file=sys.stderr, flush=True)
        if rc == EXIT_WRONG_PLATFORM:
            break  # no chip: nothing further can pass

    # the one-chip run is the data-parallel run's reference: same seed,
    # same synthetic batch, same global batch size. 5% of the loss: the
    # two programs tile their convolutions differently and bf16 sums
    # gradients across chips in bf16, over 40 updates.
    for ref, dp in (("train-f32", "train-dp4-f32"),
                    ("train-bf16", "train-dp4-bf16")):
        if not (summary.get(ref, {}).get("ok")
                and summary.get(dp, {}).get("ok")):
            continue
        a = summary[ref]["result"]["displays"]
        b = summary[dp]["result"]["displays"]
        diff = max(abs(la - lb) / abs(la) for (_, la), (_, lb) in zip(a, b))
        summary[dp]["loss_rel_diff_vs_one_chip"] = round(diff, 5)
        if [i for i, _ in a] != [i for i, _ in b] or diff > 0.05:
            failed.append(dp)
            print(f"[{dp}] FAILED: display losses {b} differ from "
                  f"{ref}'s {a} by {diff:.3f}", file=sys.stderr)

    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({"ok": not failed, "device": device, "legs": summary,
                   "wall_s": round(time.monotonic() - started, 1)}, f,
                  indent=1)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"legs": {k: ("not_run" if "not_run" in v else "ok")
                               for k, v in summary.items()}}))
    print(json.dumps({"ok": True, "device": device}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--leg", choices=sorted(LEGS),
                    help="run one leg in this process")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    return run_leg(args.leg) if args.leg else run_all()


if __name__ == "__main__":
    sys.exit(main())
