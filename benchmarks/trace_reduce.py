"""Profiler trace (xplane) -> the numbers the per-layer metrics read.

The benchmark owns this reduction so that every PR computes device busy
time, idle share, kernel time and collective exposure the same way. It reads
the `.xplane.pb` that `jax.profiler` writes with nothing but jax
(`jax.profiler.ProfileData`).

What it takes from a trace:

- device planes `/device:TPU:<n>` and, on each, the lines of XLA operations
  (`XLA Ops`), of asynchronous operations from start to done (`Async XLA
  Ops`) and of program executions (`XLA Modules`). An operation's event is
  named by its whole HLO instruction, `%name = type opcode(operands),
  attributes`, which is where opcode, fusion kind and custom-call target
  are read from;
- the benchmark's own host spans: `jax.profiler.TraceAnnotation` events
  whose names start with `bench/`, on the host plane's thread lines, and
  the runtime's `DoEnqueueProgram` host events, which carry the `run_id` of
  the program execution they launch.

Definitions:

- An operation that encloses others on its line (`while`, `conditional`,
  `call`) is a wrapper. Busy time is the union of the *leaf* operations'
  intervals, so a gap between two body operations inside a wrapper counts
  as idle. An operation's own time is its duration minus its children's.
- The window is the extent of the device operations in the trace, over
  all chips. The benchmark switches the profiler on for the traced slice
  only and drains the device before and after, so this is the slice less
  the moment before its first program starts.
- Host and device clocks disagree in a trace, by milliseconds in either
  direction. The host's spans are shifted by the smallest amount that lets
  no program start on the device before the host enqueued it (matched by
  `run_id`); what is left is the shortest launch latency, tens of
  microseconds. Without such a pair the spans are left as they are and
  `host_clock_shift_s` is null.
- Categories: a `convolution fusion` is a fusion of kind `kOutput` (on this
  compiler the fusions rooted in a convolution or a dot) or a bare
  convolution; `tpu_custom_call` is a custom call whose target is
  `tpu_custom_call`, that is a Mosaic (Pallas) kernel, and no other custom
  call (XLA:TPU emits its own, such as `ConcatBitcast`); `copy/transpose` is
  data movement (copy, slice, reshape, transpose, concatenation, and their
  asynchronous forms); `other fusion` is every other fusion.
- All-reduce time is the union of the collectives' intervals: a synchronous
  `all-reduce` event itself, or the `Async XLA Ops` event that runs from an
  `all-reduce-start` to its `all-reduce-done`. Its exposed part is where no
  other operation runs on that device.
- An idle gap is split among the `bench/` host spans open while it lasts
  (the innermost at each moment), and named by its place in the device's
  programs: between two programs, or between two operations of one.

Run `python3 benchmarks/trace_reduce.py FILE` for the summary as JSON, or
with `--describe` for the planes, lines and first events of a trace (look
at one by hand before trusting a reduction of it).
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HLO = re.compile(r"^%(?P<name>\S+) = (?P<type>.*?) (?P<op>[a-z][\w-]*)\(")
MOVES = ("copy", "slice", "reshape", "transpose", "bitcast", "concatenate",
         "reverse", "pad", "dynamic-slice", "dynamic-update-slice",
         "async-start", "async-done")
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench/"
ENQUEUE = "DoEnqueueProgram"
TOP_N = 10

CATEGORIES = ("convolution fusion", "other fusion", "tpu_custom_call",
              "all-reduce", "copy/transpose", "other")


@dataclass
class Event:
    name: str      # on a device line, the whole HLO instruction
    start: float   # ns
    end: float     # ns
    child_ns: float = 0.0
    has_child: bool = False
    run_id: int | None = None
    short: str = ""   # the instruction's own name
    op: str = ""      # its opcode
    label: str = ""   # name, opcode/fusion kind and result type

    def __post_init__(self):
        m = HLO.match(self.name)
        if m is None:
            self.short = self.label = self.name
            return
        kind = re.search(r"kind=(\w+)", self.name)
        op = f"{m['op']}/{kind[1]}" if kind else m["op"]
        result = re.sub(r"\{[^}]*\}", "", m["type"])  # drop the layouts
        self.short, self.op = m["name"], m["op"]
        self.label = f"{m['name']} [{op}] {result}"

    @property
    def own_ns(self) -> float:
        return max(self.end - self.start - self.child_ns, 0.0)


def _events(line) -> list[Event]:
    """The line's events by start, longer first. Statistics are read only
    where a `run_id` is wanted: program executions and their enqueues."""
    out = []
    for e in line.events:
        start = float(e.start_ns)
        wanted = line.name == MODULES_LINE or e.name == ENQUEUE
        out.append(Event(e.name, start, start + float(e.duration_ns),
                         run_id=dict(e.stats).get("run_id") if wanted
                         else None))
    out.sort(key=lambda ev: (ev.start, -(ev.end - ev.start)))
    return out


def _nest(events: list[Event]) -> None:
    """Mark wrappers and charge each event's duration to its parent."""
    stack: list[Event] = []
    for ev in events:  # by start, longer first: a parent precedes its children
        while stack and stack[-1].end < ev.end:
            stack.pop()   # ended, or overlaps without enclosing: a sibling
        if stack:
            stack[-1].child_ns += ev.end - ev.start
            stack[-1].has_child = True
        stack.append(ev)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint unions."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def is_allreduce(ev: Event) -> bool:
    return ev.op.startswith("all-reduce")


def is_mosaic(ev: Event) -> bool:
    """A Mosaic (Pallas) kernel, and no other custom call."""
    return (ev.op == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in ev.name)


def category(ev: Event) -> str:
    op = ev.op
    if is_allreduce(ev):
        return "all-reduce"
    if is_mosaic(ev):
        return "tpu_custom_call"
    if op == "convolution" or (op == "fusion" and "kind=kOutput" in ev.name):
        return "convolution fusion"
    if op == "fusion":
        return "other fusion"
    if op == "custom-call" or op.startswith(MOVES):
        return "copy/transpose"
    return "other"


def kernel_name(ev: Event) -> str:
    """The instruction's name without its number: the kernel's own name
    once the program gives its Pallas calls one."""
    return re.sub(r"\.\d+$", "", ev.short)


def _allreduce_intervals(leaves: list[Event], asyncs: list[Event]):
    """One interval per collective: a synchronous all-reduce event, or the
    asynchronous one from its start to its done."""
    if any(ev.op == "all-reduce-start" for ev in leaves) \
            and not asyncs:
        raise ValueError(f"all-reduce-start events but no {ASYNC_LINE!r} "
                         f"line to give their extent")
    return ([(ev.start, ev.end) for ev in leaves
             if ev.op == "all-reduce"]
            + [(ev.start, ev.end) for ev in asyncs if is_allreduce(ev)])


def _host_events(profile) -> tuple[list[Event], dict[int, float]]:
    """The benchmark's spans, and when the host enqueued each program
    execution ({run_id: ns})."""
    spans, enqueued = [], {}
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in _events(line):
                if ev.name.startswith(HOST_PREFIX):
                    spans.append(ev)
                elif ev.name == ENQUEUE and ev.run_id is not None:
                    enqueued[ev.run_id] = ev.start
    spans.sort(key=lambda ev: (ev.start, -(ev.end - ev.start)))
    return spans, enqueued


NO_SPAN = "outside the benchmark's spans"


def _timeline(spans: list[Event]) -> list[tuple[float, float, str]]:
    """The spans flattened to consecutive (start, end, label) segments, each
    labelled by the innermost span open in it (the one that began last; of
    two that began together, the shorter)."""
    times = sorted({t for ev in spans for t in (ev.start, ev.end)})
    out, open_, nxt = [], [], 0
    for t0, t1 in zip(times, times[1:]):
        while nxt < len(spans) and spans[nxt].start <= t0:
            open_.append(spans[nxt])
            nxt += 1
        open_ = [ev for ev in open_ if ev.end > t0]
        if open_:
            inner = max(open_, key=lambda ev: (ev.start, ev.start - ev.end))
            name = inner.name[len(HOST_PREFIX):]
            label = f"inside {name}" if name == "solver.step" else name
        else:
            label = NO_SPAN
        out.append((t0, t1, label))
    return out


def _blame(timeline, starts: list[float], lo: float, hi: float):
    """Split the interval [lo, hi] among the host segments it crosses:
    yields (label, ns)."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    t = lo
    while t < hi:
        if i >= len(timeline) or timeline[i][0] > t:
            end = min(hi, timeline[i][0] if i < len(timeline) else hi)
            yield NO_SPAN, end - t
        else:
            end = min(hi, timeline[i][1])
            if end > t:
                yield timeline[i][2], end - t
            i += 1
        t = max(t, end)


def _position(lo: float, hi: float, modules: list[Event],
              starts: list[float], leaves: list[Event],
              leaf_starts: list[float]) -> str:
    """Where the gap [lo, hi] lies in the device's programs."""
    i = bisect.bisect_right(starts, lo) - 1
    if i >= 0 and modules[i].end >= hi:
        k = bisect.bisect_right(leaf_starts, lo)
        prev_op = leaves[k - 1].short if k else "start"
        next_op = leaves[k].short if k < len(leaves) else "end"
        return f"within {modules[i].name}: {prev_op} -> {next_op}"
    prev_m = modules[i].name if i >= 0 else "start"
    next_m = modules[i + 1].name if i + 1 < len(modules) else "end"
    return f"between programs: {prev_m} -> {next_m}"


def _module_name(name: str) -> str:
    """`jit_step(1234567)` -> `jit_step`: the run id is not the program."""
    return re.sub(r"\(\d+\)$", "", name)


def _reduce_device(ops, modules, asyncs, timeline, lo: float,
                   hi: float) -> dict:
    """One device plane over the window [lo, hi], which holds every one of
    its operations; times in seconds."""
    leaves = [ev for ev in ops if not ev.has_child]
    busy = _union([(ev.start, ev.end) for ev in leaves])
    out = {"busy_s": _total(busy) / 1e9}
    for key in ("by_category_s", "by_op_s", "kernel_calls", "kernel_s",
                "gaps_s", "program_calls", "program_s"):
        out[key] = defaultdict(float)
    for ev in ops:
        out["by_category_s"][category(ev)] += ev.own_ns / 1e9
        out["by_op_s"][ev.label] += ev.own_ns / 1e9
        if is_mosaic(ev):
            out["kernel_calls"][kernel_name(ev)] += 1
            out["kernel_s"][kernel_name(ev)] += (ev.end - ev.start) / 1e9
    for m in modules:
        out["program_calls"][m.name] += 1
        out["program_s"][m.name] += (m.end - m.start) / 1e9

    collectives = _allreduce_intervals(leaves, asyncs)
    span = _union(collectives)
    compute = _union([(ev.start, ev.end) for ev in leaves
                      if not is_allreduce(ev)])
    out["allreduce"] = {
        "count": len(collectives), "seconds": _total(span) / 1e9,
        "exposed_seconds": (_total(span) - _overlap(span, compute)) / 1e9}

    starts = [m.start for m in modules]
    leaf_starts = [ev.start for ev in leaves]
    segment_starts = [seg[0] for seg in timeline]
    edge = lo
    for s, e in busy + [(hi, hi)]:
        if s > edge:
            where = _position(edge, s, modules, starts, leaves, leaf_starts)
            for host, ns in _blame(timeline, segment_starts, edge, s):
                out["gaps_s"][f"{host} | {where}"] += ns / 1e9
        edge = max(edge, e)
    return out


def _mean(values: list):
    """Mean over devices, through nested tables; an operation, kernel or
    gap that one device lacks counts as 0 there."""
    if isinstance(values[0], dict):
        keys = sorted({k for v in values for k in v})
        return {k: _mean([v.get(k, 0.0) for v in values]) for k in keys}
    return sum(values) / len(values)


def reduce_profile(profile) -> dict | None:
    """Summary of a trace, or None when it holds no TPU device plane (a
    CPU rehearsal)."""
    planes = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
        if not lines.get(OPS_LINE):
            raise ValueError(f"{plane.name}: no events on a {OPS_LINE!r} "
                             f"line (has {sorted(lines)})")
        _nest(lines[OPS_LINE])
        for m in lines.get(MODULES_LINE, []):
            m.name = _module_name(m.name)
        planes.append((plane.name, lines))
    if not planes:
        return None

    spans, enqueued = _host_events(profile)
    lead = [enqueued[m.run_id] - m.start for _, lines in planes
            for m in lines.get(MODULES_LINE, []) if m.run_id in enqueued]
    shift = max(lead) if lead else None
    for ev in spans:  # onto the device's clock
        ev.start -= shift or 0.0
        ev.end -= shift or 0.0

    timeline = _timeline(spans)
    ops = [ev for _, lines in planes for ev in lines[OPS_LINE]]
    lo, hi = min(ev.start for ev in ops), max(ev.end for ev in ops)
    devices = {name: _reduce_device(lines[OPS_LINE],
                                    lines.get(MODULES_LINE, []),
                                    lines.get(ASYNC_LINE, []), timeline,
                                    lo, hi)
               for name, lines in planes}
    mean = _mean(list(devices.values()))
    top = lambda table: [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:TOP_N]]
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "n_devices": len(devices),
        "host_clock_shift_s": None if shift is None else shift / 1e9,
        "busy_s": mean["busy_s"],
        "idle_share": 1.0 - mean["busy_s"] / window_s,
        "busy_s_per_device": {k: d["busy_s"] for k, d in devices.items()},
        "by_category_s": {c: mean["by_category_s"].get(c, 0.0)
                          for c in CATEGORIES},
        "device_ops": top(mean["by_op_s"]),
        "idle_gaps": top(mean["gaps_s"]),
        "custom_calls": {k: {"count": n, "seconds": mean["kernel_s"][k]}
                         for k, n in mean["kernel_calls"].items()},
        "allreduce": mean["allreduce"],
        "programs": {k: {"count": n, "seconds": mean["program_s"][k]}
                     for k, n in mean["program_calls"].items()},
    }


def reduce_xplane(path: str) -> dict | None:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def describe(path: str, first: int = 8) -> str:
    """Planes, lines, event counts and the first events of each line."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:first]:
                out.append(f"    {e.name!r} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={dict(e.stats)}")
    return "\n".join(out)


def main(argv: list[str]) -> int:
    args = [a for a in argv if not a.startswith("--")]
    if len(args) != 1:
        print(__doc__)
        return 2
    if "--describe" in argv:
        print(describe(args[0]))
    else:
        print(json.dumps(reduce_xplane(args[0]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
