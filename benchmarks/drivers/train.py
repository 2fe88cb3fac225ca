"""The `train` driver: one training job, dispatched as
`caffe train -solver <recipe> -synthetic [-gpu all] [-precision bf16]`
dispatches it.

The recipe is run as committed: `step_chunk`, the guard and `display` are
what the solver prototxt says. Test and snapshot cadence are off and
`max_iter` is out of reach, so the window holds training steps only. What
the job shape adds is in the traffic file: precision, mesh, batch per chip,
the synthetic feed, and the length of a `solver.step` block.

Timeline of a run (all of it before the window is set-up, counted from
`t0`, the moment the harness found the device runtime up):

1. build the `Solver` from the recipe, weights from `--seed`;
2. make the synthetic batch on the device from `--seed` in one jitted
   call, and the reference sample in another;
3. compare the system's logits on the fresh weights with the plain
   reference (`reference/cnn_ref.py`);
4. warm up: `solver.step(1)` (iteration 0, which compiles the step and
   crosses a display boundary), then one block, so that every later block
   ends on a block boundary;
5. the window: `solver.step(block)` until `--seconds` have passed, one
   `block_until_ready` at the end. With `--trace 1` a few blocks after the
   first run under the profiler; starting and stopping it is timed apart.

From the program this takes `Solver`, `Net`, `MeshPlan`, the two prototxt
parsers and the compile-cache rule, and reads `Solver`'s counters.
"""

from __future__ import annotations

import copy
import json
import math
import shutil
import statistics
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
from reference import cnn_ref  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts programs jax builds (compiled or loaded from the persistent
    cache) and, of those, the cache hits. Listeners live as long as the
    process: jax has no public call to remove one."""

    def __init__(self):
        self.built = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.built += 1

    def _event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.built - self.hits


def set_input_dims(npar, batch: int, hw: tuple[int, int] | None = None):
    """Rewrite the Input layers' batch (and, for a rehearsal preset, the
    image size) in place. Never a width: channels are left alone."""
    for layer in npar.layer:
        if layer.type != "Input":
            continue
        for shape in layer.input_param.shape:
            shape.dim[0] = batch
            if hw is not None and len(shape.dim) == 4:
                shape.dim[2], shape.dim[3] = hw


def make_arrays(key, specs: dict, label_classes: int, shardings=None):
    """Every feed of `specs` ({name: shape}) from one key in one jitted
    call: class ids for 1-D feeds, unit normals for the rest."""
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(specs.items())):
            k = jax.random.fold_in(key, i)
            if len(shape) == 1:
                out[name] = jax.random.randint(k, shape, 0, label_classes,
                                               jnp.int32)
            else:
                out[name] = jax.random.normal(k, shape, jnp.float32)
        return out
    return jax.jit(make, out_shardings=shardings)(key)


def peak_device_bytes(devices) -> tuple[int, dict]:
    """Peak memory on the fullest chip, with that chip's raw statistics.

    On this runtime `peak_bytes_in_use` counts live buffers only; what a
    running program needs for its temporaries is reserved apart
    (`peak_bytes_reserved`), and for a train step that is most of the memory.
    During the window the buffers live at its end (weights, optimizer state,
    the batch) are held all along and the step's reservation on top of them,
    so the peak is at least their sum; it is also at least the allocator's
    own peak of live buffers, which set-up may have reached."""
    best, fullest = 0, {}
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
        if peak >= best:
            best, fullest = peak, stats
    return best, fullest


def quartiles(values: list[float]) -> tuple[float, float, float]:
    if len(values) < 2:
        return (values[0],) * 3
    q1, q2, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return q1, q2, q3


def logits_check(cell: dict, job: "Job", ref_net: dict, key) -> dict:
    """Relative RMS distance between the system's logits and the plain
    reference's, on a seeded sample and the solver's fresh weights."""
    from caffe_mpi_tpu.net import Net

    spec = {**cell["config"]["checks"]["logits"], **cell["preset"]}
    phase, n = spec["phase"], spec["sample"]
    blob = cnn_ref.logits_blob(ref_net, phase)
    sample_par = copy.deepcopy(job.npar)
    set_input_dims(sample_par, n, job.hw)
    net = Net(sample_par, phase=phase, precision=job.precision)
    specs = {name: shape for name, (shape, _) in net.feed_specs.items()}
    one = jax.devices()[0]
    feeds = make_arrays(key, specs, 1)  # labels all 0: the logits ignore them
    params, state = jax.device_put(
        (job.solver.params, job.solver.net_state), one)
    train = phase == "TRAIN"

    def system(params, state, feeds):
        blobs, _, _ = net.apply(params, state, feeds, train=train,
                                rng=jax.random.PRNGKey(0))
        return blobs[blob].astype(jnp.float32)

    def reference(params, state, feeds):
        return cnn_ref.forward(ref_net, phase, params, state, feeds)[blob]

    got = np.asarray(jax.jit(system)(params, state, feeds), np.float64)
    want = np.asarray(jax.jit(reference)(params, state, feeds), np.float64)
    rel_rms = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    limit = spec["rel_rms_max"][job.precision]
    return {"blob": blob, "phase": phase, "sample": n,
            "rel_rms": rel_rms, "rel_rms_max": limit,
            "reference_rms": float(np.sqrt(np.mean(want ** 2))),
            "finite": bool(np.isfinite(got).all()),
            "ok": bool(np.isfinite(got).all() and rel_rms <= limit)}


@dataclass
class Job:
    """One cell's training job, built and ready to step."""
    solver: object
    net_text: str
    npar: object
    plan: object
    precision: str
    batch: int          # samples per iteration, over all chips
    block: int          # iterations per `solver.step` call
    hw: tuple | None    # a rehearsal preset's image size


def build_job(cell: dict, seed: int, out_dir: Path, devices=None) -> Job:
    """The Solver as `caffe train -solver <recipe>` builds it, with the
    job shape of the traffic file. `devices` places the data-parallel mesh;
    None builds it without one (the deviceless compile binds its own)."""
    from caffe_mpi_tpu.parallel import MeshPlan
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver

    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    root = BENCH.parent
    per_chip = preset.get("batch_per_chip", traffic["batch_per_chip"])
    hw = tuple(preset["input_hw"]) if "input_hw" in preset else None
    batch = per_chip * cell["chips"]
    sp = SolverParameter.from_file(str(root / config["recipe"]["solver"]))
    net_text = (root / sp.net).read_text()
    npar = NetParameter.from_text(net_text)
    set_input_dims(npar, batch, hw)
    sp.net, sp.net_param = "", npar
    sp.random_seed = seed
    sp.precision = traffic["precision"]
    sp.max_iter = 10 ** 9
    sp.test_iter, sp.test_interval = [], 0
    sp.snapshot, sp.snapshot_after_train = 0, False
    sp.snapshot_prefix = str(out_dir / "snapshot")
    block = traffic["block_iters"]
    if sp.display % block:
        raise ValueError(
            f"block_iters {block} does not divide the recipe's display "
            f"interval {sp.display}: blocks would not end on its boundaries")
    if traffic["mesh"] not in ("none", "data_parallel"):
        raise ValueError(f"unknown mesh {traffic['mesh']!r}")
    plan = None
    if traffic["mesh"] == "data_parallel" and devices is not None:
        plan = MeshPlan.data_parallel(devices)
    solver = Solver(sp, model_dir=str(root), mesh=plan)
    return Job(solver, net_text, npar, plan, traffic["precision"], batch,
               block, hw)


def measure(job: Job, feed_fn, *, seconds: float, trace_spec: dict | None,
            out_dir: Path, counter: CompileCounter) -> dict:
    """Warm up, then run the window. Returns what the window showed; the
    clock readings are `time.perf_counter` seconds."""
    clock = time.perf_counter
    solver, block = job.solver, job.block
    t_start = clock()
    losses = [(0, solver.step(1, feed_fn))]
    t_compiled = clock()
    loss = solver.step(block, feed_fn)
    losses.append((solver.iter - 1, loss))
    jax.block_until_ready(solver.params)

    blocks: list[dict] = []

    def one_block(traced: bool = False):
        t = clock()
        with jax.profiler.TraceAnnotation("bench/solver.step"):
            loss = solver.step(block, feed_fn)
        blocks.append({"s": clock() - t, "traced": traced})
        losses.append((solver.iter - 1, loss))

    profiler_s = 0.0
    xplane = None
    built0 = counter.built
    d0, h0 = solver.dispatch_count, solver.host_sync_count
    g0, s0 = solver.guard_sync_count, solver.skipped_steps
    t_begin = clock()
    if trace_spec is not None:
        for _ in range(trace_spec["skip_blocks"]):
            one_block()
        trace_dir = out_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        t = clock()
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        profiler_s += clock() - t
        for _ in range(trace_spec["blocks"]):
            one_block(traced=True)
        t = clock()
        jax.profiler.stop_trace()
        profiler_s += clock() - t
        xplane = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    while not blocks or clock() - t_begin - profiler_s < seconds:
        one_block()
    jax.block_until_ready(solver.params)
    return {
        "t_begin": t_begin,
        "setup_compile_s": t_compiled - t_start,
        "window_s": clock() - t_begin - profiler_s,
        "profiler_s": profiler_s,
        "blocks": blocks, "losses": losses, "xplane": xplane,
        "compiles_in_window": counter.built - built0,
        "dispatches": solver.dispatch_count - d0,
        "host_syncs": solver.host_sync_count - h0,
        "guard_syncs": solver.guard_sync_count - g0,
        "skipped_steps": solver.skipped_steps - s0,
        "overflow_steps": solver.overflow_steps,
        "loss_scale": solver.loss_scale_value,
    }


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t0: float, out_dir: Path, say) -> dict:
    """Run the cell once. `cell` holds the workload entry, its
    configuration and traffic files, and `preset` (empty on the chip, the
    configuration's tiny preset in a rehearsal). `say(**fields)` prints one
    earlier line. Returns the harness's run record."""
    clock = time.perf_counter
    counter = CompileCounter()
    config, traffic, preset = cell["config"], cell["traffic"], cell["preset"]
    chips = cell["chips"]
    devices = jax.devices()[:chips]
    job = build_job(cell, seed, out_dir, devices)
    solver, plan, batch, block = job.solver, job.plan, job.batch, job.block
    t_built = clock()
    try:
        # inputs from the seed, on the device, laid out as the step reads them
        specs = {k: shape for k, (shape, _) in solver.net.feed_specs.items()}
        shardings = None if plan is None else {
            k: plan.batch_sharded(len(shape), 0)
            for k, shape in specs.items()}
        key = jax.random.PRNGKey(seed)
        feeds = make_arrays(jax.random.fold_in(key, 1), specs,
                            traffic["feed"]["label_classes"], shardings)
        ref_net = cnn_ref.parse_prototxt(job.net_text)
        cnn_ref.set_input_dims(ref_net, batch, job.hw)
        logits = logits_check(cell, job, ref_net, jax.random.fold_in(key, 2))
        say(check="logits", **logits)
        t_checked = clock()
        seen = measure(job, lambda it: feeds, seconds=seconds,
                       trace_spec=traffic["trace"] if trace else None,
                       out_dir=out_dir, counter=counter)
    finally:
        solver.close()
    peak_bytes, fullest = peak_device_bytes(devices)
    say(memory_peak_bytes=peak_bytes, memory_stats_of_fullest_chip=fullest)

    blocks, losses, window_s = seen["blocks"], seen["losses"], seen["window_s"]
    iters = block * len(blocks)
    q1, med, q3 = quartiles([1e3 * b["s"] / block for b in blocks])
    plain = [b for b in blocks if not b["traced"]]
    plain_rate = (batch * block * len(plain)
                  / sum(b["s"] for b in plain)) if plain else None
    traced_iters = block * (len(blocks) - len(plain))
    nonfinite = sum(1 for _, loss in losses if not math.isfinite(loss))
    loss_spec = {**config["checks"]["loss"], **preset.get("loss", {})}
    first = losses[0][1]
    reached = [(it, loss) for it, loss in losses
               if it >= loss_spec["by_iteration"]]
    loss_ok = bool(reached) and reached[0][1] <= loss_spec["share_max"] * first
    say(check="loss", first=first, reached=reached[:1], last=losses[-1],
        share_max=loss_spec["share_max"],
        by_iteration=loss_spec["by_iteration"], nonfinite=nonfinite,
        ok=loss_ok, trajectory=losses[:12])
    counts = {k: seen[k] for k in (
        "dispatches", "host_syncs", "guard_syncs", "skipped_steps",
        "overflow_steps", "loss_scale", "compiles_in_window", "profiler_s")}
    setup_s = seen["t_begin"] - t0
    parts = {"setup_build_s": t_built - t0,
             "setup_check_s": t_checked - t_built,
             "setup_compile_s": seen["setup_compile_s"]}
    say(setup_s=setup_s, **parts,
        setup_warm_block_s=setup_s - sum(parts.values()))
    say(window_s=window_s, blocks=len(blocks), block_iters=block,
        iters=iters, samples_per_iter=batch, step_ms_median=med,
        step_ms_q1=q1, step_ms_q3=q3, block_s=[b["s"] for b in blocks],
        block_end_syncs=len(blocks),
        programs_built=counter.built, cache_hits=counter.hits,
        compiled=counter.compiled, **counts)

    summary = None
    pallas_ok = True
    if seen["xplane"] is not None:
        summary = trace_reduce.reduce_xplane(str(seen["xplane"]))
        (out_dir / "trace_summary.json").write_text(
            json.dumps(summary, indent=1))
    if summary is not None:
        expected = config["checks"]["pallas_calls_per_step"][job.precision]
        calls = sum(k["count"] for k in summary["custom_calls"].values())
        pallas_ok = calls == expected * traced_iters
        say(check="pallas", calls_in_slice=calls, traced_iters=traced_iters,
            expected_per_step=expected, ok=pallas_ok)

    rate = batch * iters / window_s
    record = {
        "cell": cell["name"], "chips": chips, "precision": job.precision,
        "samples_per_iter": batch, "block_iters": block, "iters": iters,
        "window_s": window_s,
        "untraced_samples_per_s": plain_rate, "traced_iters": traced_iters,
        "programs_built": counter.built, "cache_hits": counter.hits,
        "setup_s": setup_s, **parts, "memory_peak_bytes": peak_bytes,
        "macs_per_sample": cnn_ref.macs_per_sample(ref_net),
        "step_ms": {"q1": q1, "median": med, "q3": q3},
        "logits": logits, "losses": losses, **counts,
    }
    return {
        "correct": bool(logits["ok"] and loss_ok and nonfinite == 0
                        and seen["compiles_in_window"] == 0 and pallas_ok),
        "attempted": iters,
        "failed": seen["skipped_steps"] + nonfinite,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "record": record,
        "trace": summary,
    }
