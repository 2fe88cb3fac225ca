"""Set-up, reduced from the program's own start-up ledger.

The program keeps one ledger a process (caffe_mpi_tpu/utils/spans.py
`ledger`, utils/compile_cache.py `programs`): phases of `Net` and `Solver`
construction, one row and the events of every program jax traced, lowered
and built, Python seconds inside layer applies by layer type, one phase a
trace of a Pallas kernel. All of it is stamped on `time.perf_counter`, the
clock `run.py` and the drivers read, and the benchmark runs in the
program's process: a reader (`layer_metrics/setup_*_s.py`) asks for the
ledger's snapshot after the driver returned and reduces it here.

Set-up, on the ledger's clock, is [installed_at, installed_at + setup_s]:
`run.py` calls `enable_compile_cache()`, which installs the ledger's
listeners, within milliseconds of the `t0` it counts `setup_s` from. In a
rehearsal no cache is enabled and the first `Net` installs them, so the
interval starts at that `Net` and reaches some way into the window; no
number of a rehearsal is printed.

The account, exact by construction:

    setup_s = top-level phases (their union; `trace/*` left out: those
                  lie inside a program's trace)
            + trace, lower and backend seconds of the build events that
                  start outside every top-level phase
            + setup_unaccounted_s

so `setup_unaccounted_s` is what the ledger cannot see, as a number: the
device running the checks and the warm-up, imports, eager dispatch, the
harness's own Python. An event that lies inside another on its thread was
folded into it by the ledger, so no second is counted twice.

A program without a ledger (a commit before PR 35) gives None for every
metric here, and the result line leaves them out.

The first call on a live run prints one earlier line, `startup_ledger`:
the account above, seconds by phase name, the programs' sums with hits
and misses, the ten programs that cost most, seconds by layer type and by
kernel and arm, and what was built inside the window (nothing, or
`correct` is false by the driver's own count). It is also written to
`chiprun_out/bench/<cell>/startup.json`.
"""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
STEP_PROGRAMS = ("step", "multi_step")     # `Solver`'s fixed names
BUILD_PHASES = ("parse", "net/build")
FILL_PHASES = ("net/fill", "solver/opt state", "solver/place")
KERNEL_PHASE = "trace/kernel"
KINDS = ("trace", "lower", "backend")
_reduced: dict[int, dict | None] = {}      # id(run record) -> its reduction


def snapshot_of(run: dict) -> dict | None:
    """The ledger's snapshot: the one a test put into the record, else this
    process's; None where the program has none."""
    if "startup_ledger" in run:
        return run["startup_ledger"]
    try:
        from caffe_mpi_tpu.utils import spans
        return spans.ledger.snapshot()
    except (ImportError, AttributeError):
        return None


def outermost(intervals: list[tuple]) -> list[tuple]:
    """Of (start, end, ...) tuples, those that lie inside no other."""
    out, covered = [], float("-inf")
    for item in sorted(intervals, key=lambda i: (i[0], -i[1])):
        if item[1] > covered:
            out.append(item)
            covered = item[1]
    return out


def reduce(snapshot: dict, setup_s: float, window_s: float = 0.0) -> dict | None:
    """The ten metrics and what the earlier line adds, from one snapshot."""
    programs = snapshot["programs"]
    t0 = programs["installed_at"]
    if t0 is None:
        return None
    t1 = t0 + setup_s
    clip = lambda a, b: max(0.0, min(b, t1) - max(a, t0))
    phases = [(start, end, name, depth, stats)
              for name, start, end, depth, stats in snapshot["phases"]]

    def seconds(*names: str) -> float:
        return sum(clip(p[0], p[1]) for p in outermost(
            [p for p in phases if p[2] in names]))

    top = outermost([p for p in phases
                     if p[3] == 0 and not p[2].startswith("trace/")])
    top_s = sum(clip(p[0], p[1]) for p in top)
    by_phase = {name: {"s": seconds(name), "top_level_s": 0.0,
                       "n": sum(p[2] == name and clip(p[0], p[1]) > 0
                                for p in phases)}
                for name in sorted({p[2] for p in phases})}
    for start, end, name, *_ in top:
        by_phase[name]["top_level_s"] += clip(start, end)

    sums = {f"{k}_s": 0.0 for k in KINDS} | {"built": 0, "hits": 0,
                                            "misses": 0}
    outside = {f"{k}_s": 0.0 for k in KINDS}
    step_s, in_window, by_program = 0.0, 0, {}
    for name, kind, start, end, *rest in programs["events"]:
        by_kind, (built, hits, misses) = rest[:3], rest[3:]
        if t1 < end <= t1 + window_s:
            in_window += built
        if not t0 <= end <= t1:
            continue
        row = by_program.setdefault(name, {f"{k}_s": 0.0 for k in KINDS}
                                    | {"built": 0, "hits": 0})
        for k, s in zip(KINDS, by_kind):
            sums[f"{k}_s"] += s
            row[f"{k}_s"] += s
        sums["built"] += built
        sums["hits"] += hits
        sums["misses"] += misses
        row["built"] += built
        row["hits"] += hits
        if name in STEP_PROGRAMS:
            step_s += end - start
        if not any(p[0] <= start <= p[1] for p in top):
            for k, s in zip(KINDS, by_kind):
                outside[f"{k}_s"] += s
    outside_s = sum(outside.values())

    kernels: dict[str, dict] = {}
    for start, end, _, _, stats in outermost(
            [p for p in phases if p[2] == KERNEL_PHASE]):
        if clip(start, end) > 0:
            key = f"{stats.get('kernel', '?')}/{stats.get('branch', '?')}"
            row = kernels.setdefault(key, {"s": 0.0, "n": 0})
            row["s"] += clip(start, end)
            row["n"] += 1

    cost = lambda row: sum(row[f"{k}_s"] for k in KINDS)
    metrics = {
        "setup_net_build_s": seconds(*BUILD_PHASES),
        "setup_fill_s": seconds(*FILL_PHASES),
        "setup_trace_s": sums["trace_s"],
        "setup_lower_s": sums["lower_s"],
        "setup_backend_s": sums["backend_s"],
        "setup_programs_built": sums["built"],
        "setup_step_program_s": step_s,
        "setup_layer_apply_s": sum(snapshot["apply_s"].values()),
        "setup_kernel_trace_s": sum(k["s"] for k in kernels.values()),
        "setup_unaccounted_s": setup_s - top_s - outside_s,
    }
    return {
        "metrics": metrics,
        "account": {"setup_s": setup_s, "top_level_phases_s": top_s,
                    "programs_outside_phases_s": outside_s,
                    "programs_outside_phases": outside,
                    "unaccounted_s": metrics["setup_unaccounted_s"]},
        "phases": by_phase,
        "programs": sums,
        "programs_largest": dict(sorted(
            by_program.items(), key=lambda kv: -cost(kv[1]))[:10]),
        "apply_s_by_layer_type": dict(sorted(
            snapshot["apply_s"].items(), key=lambda kv: -kv[1])),
        "kernel_traces": dict(sorted(kernels.items(),
                                     key=lambda kv: -kv[1]["s"])),
        "built_in_window": in_window,
        "dropped": {"phases": snapshot["phases_dropped"],
                    "build_events": programs["dropped"]},
    }


def of_run(run: dict) -> dict | None:
    """The reduction of this run's ledger, made once; on a live run its
    first call prints the `startup_ledger` earlier line."""
    if id(run) not in _reduced:
        snapshot = snapshot_of(run)
        reduced = None if snapshot is None else reduce(
            snapshot, run["setup_s"],
            run.get("window_s", 0.0) + run.get("profiler_s", 0.0))
        _reduced.clear()
        _reduced[id(run)] = reduced
        if reduced is not None and "startup_ledger" not in run:
            line = json.dumps({"startup_ledger": reduced})
            print(line, flush=True)
            out_dir = ROOT / "chiprun_out" / "bench" / run["cell"]
            out_dir.mkdir(parents=True, exist_ok=True)
            (out_dir / "startup.json").write_text(line + "\n")
    return _reduced[id(run)]


def metric(run: dict, name: str):
    reduced = of_run(run)
    return None if reduced is None else reduced["metrics"][name]
