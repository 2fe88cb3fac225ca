"""Profiler trace (xplane) -> device time per prototxt layer and phase, and
the program's own host spans laid against the device's idle gaps.

`trace_reduce.py` reads what any XLA program leaves in a trace. This file
reads what the program itself writes there (`caffe_mpi_tpu/utils/spans.py`,
whose docstring fixes the grammar):

- device scopes. A layer's `apply` runs under `caffe.<Type>.<name>` (the
  name percent-encoded), the update under `solver.update`, the bucketed
  gradient psums under `solver.reduce`. XLA keeps the scope stack in each
  operation's `op_name`; the profiler stores it as the `tf_op` statistic of
  the operation's *event metadata*, beside `flops` and `bytes_accessed`.
  `jax.profiler.ProfileData` does not show event-metadata statistics and
  no `xplane_pb2` is installed, so `event_metadata` walks the protobuf wire
  format for just those maps;
- host spans `caffe/...` (`TraceAnnotation`s) on the host plane's thread
  lines, with `caffe/solver/iter` around each pass of `Solver.step`'s loop.

Definitions:

- An operation's time is its event's duration, for the *leaf* events of
  the `XLA Ops` line (`trace_reduce`'s `Event` / `_nest`): the same events
  whose union is `busy_s` there. It goes to one (layer type, layer name,
  phase). XLA fuses across layers, and a fusion's `tf_op` names its root
  alone (on this compiler never more than one name, whatever `a;b:`
  allows): `fusion.256` of AlexNet's f32 step is named after conv1's bias
  gradient and holds ReLU's and most of LRN's backward pass. So the
  instructions a fusion holds are read from the program's HLO, which the
  profiler keeps in the trace (`hlo_op_names`). A convolution fusion
  (`trace_reduce.category`) goes to its root's scope: the convolution is
  its work, the rest an epilogue. Any other operation goes to the scope
  most of its scoped instructions carry (of nested layer scopes the
  innermost; the first on a tie); without any it is unscoped.
  `outvoted_share` is the time-weighted share of instructions that carry
  another scope than the one their operation went to: the error of the
  rule, if instructions of one fusion cost alike.
- Phase: `backward` if the chosen name holds `transpose(` (reverse mode,
  rematerialised forward included), else `forward` (an un-differentiated
  pass such as serving's counts here); `update` and `reduce` for the
  solver scopes; `unscoped` for the rest (compiler copies, eager helpers).
- A layer's roofline share is the least time the chip could take for its
  operations' `flops` and `bytes_accessed` (the larger of flops / peak and
  bytes / peak) over the time they took.
- Host spans are shifted onto the device clock by the shift `trace_reduce`
  computes. A span's self time is its duration less the `caffe/` spans
  nested in it on its thread.
- A between-programs idle gap (`trace_reduce._position`) is split among
  the `caffe/solver/*` spans open while it lasted, the innermost at each
  moment; `iter` is the loop pass itself outside any child span.

Per device means throughout, as in `trace_reduce`. A trace of a program
without scopes or spans (an earlier commit) reduces without error: every
operation is unscoped, the span tables are empty, and the readers in
`layer_metrics/` that need a scope or a span return None.

`python3 benchmarks/span_reduce.py FILE` prints both tables as JSON, for
a `caffe train -profile` trace too. Imported, it expects `benchmarks/` on
`sys.path`, where the driver and the tests' conftest put it.
"""

from __future__ import annotations

import functools
import json
import re
import struct
import sys
from collections import Counter, defaultdict
from pathlib import Path
from urllib.parse import unquote

import trace_reduce
from trace_reduce import (DEVICE_PLANE, HOST_PLANE, MODULES_LINE, NO_SPAN,
                          OPS_LINE, _blame, _events, _mean, _module_name,
                          _nest, _position, _total, _union)

BENCH = Path(__file__).resolve().parent
LAYER_SCOPE = re.compile(r"caffe\.([A-Za-z0-9_]+)\.([A-Za-z0-9_.~%-]*)")
SOLVER_SCOPES = {"solver.update": "update", "solver.reduce": "reduce"}
PHASES = ("forward", "backward", "update", "reduce", "unscoped")
SPAN_PREFIX = "caffe/"
SOLVER_PREFIX = "caffe/solver/"
OUTSIDE = "outside Solver.step"
HLO_PLANE = "/host:metadata"
TOP_N = 10


# -- the protobuf wire walk --------------------------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and stat_metadata =
# 5 (maps: key = 1, value = 2), stats = 6; XEventMetadata: name = 2, stats =
# 5; XStatMetadata: name = 2; XStat: metadata_id = 1, double = 2, uint64 = 3,
# int64 = 4, str = 5, ref = 7 (a ref points into stat_metadata).

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a message: an int for a
    varint, a memoryview for the rest."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _collect(buf, wanted: tuple) -> dict:
    """{field number: [values]} of a message, for the `wanted` fields."""
    out = defaultdict(list)
    for field, value in _fields(buf):
        if field in wanted:
            out[field].append(value)
    return out


def _text(values) -> str:
    return bytes(values[0]).decode(errors="replace") if values else ""


def _stats(stats: list, stat_names: dict) -> dict:
    """{statistic name: value} of a message's XStat fields."""
    out = {}
    for stat in stats:
        parts = dict(_fields(stat))
        name = stat_names.get(parts.get(1))
        if 5 in parts:
            out[name] = _text([parts[5]])
        elif 7 in parts:
            out[name] = stat_names.get(parts[7], "")
        elif 2 in parts:
            out[name] = struct.unpack("<d", parts[2])[0]
        elif 6 in parts:
            out[name] = parts[6]
        elif 3 in parts or 4 in parts:
            out[name] = parts.get(3, parts.get(4))
    return out


def _planes(data: bytes):
    """(plane name, the plane's own statistics, {event metadata id: (event
    name, its statistics)}) of each plane."""
    for number, plane in _fields(memoryview(data)):
        if number != 1:
            continue
        parts = _collect(plane, (2, 4, 5, 6))
        stat_names = {}
        for entry in parts[5]:
            entry = dict(_fields(entry))
            stat_names[entry[1]] = _text(_collect(entry[2], (2,))[2])
        events = {}
        for entry in parts[4]:
            entry = dict(_fields(entry))
            meta = _collect(entry[2], (2, 5))
            events[entry[1]] = (_text(meta[2]), _stats(meta[5], stat_names))
        yield _text(parts[2]), _stats(parts[6], stat_names), events


def event_metadata(data: bytes) -> dict:
    """{device plane: {"stats": the plane's own, "events": {event name:
    its metadata's statistics}}}."""
    return {name: {"stats": stats, "events": dict(events.values())}
            for name, stats, events in _planes(data)
            if DEVICE_PLANE.match(name)}


# The profiler also keeps each program's HLO (plane `/host:metadata`, one
# event metadata per program, keyed by its id, with the statistic `Hlo
# Proto`). HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto: instructions = 2, id = 5; HloInstructionProto: name =
# 1, metadata = 7 (OpMetadata.op_name = 2), called_computation_ids = 38.

def _fused_op_names(hlo) -> dict:
    """{instruction: the `op_name`s of every instruction it fuses or
    calls} of one HloProto, for the instructions that call a computation.
    Instruction names are unique in a module."""
    own, calls, bodies = {}, {}, {}
    for computation in _collect(dict(_fields(hlo))[1], (3,))[3]:
        computation = _collect(computation, (2, 5))
        body = bodies[computation[5][0]] = []
        for instruction in computation[2]:
            parts = _collect(instruction, (1, 7, 38))
            name = _text(parts[1])
            body.append(name)
            own[name] = _text(_collect(parts[7][0], (2,))[2]) \
                if parts[7] else ""
            calls[name] = [c for packed in parts[38] for c in (
                [packed] if isinstance(packed, int) else _varints(packed))]

    def fused(instruction, seen=()):
        found = []
        for c in calls[instruction]:
            if c not in seen:
                for inner in bodies.get(c, []):
                    found += [own[inner]] + fused(inner, seen + (c,))
        return [name for name in found if name]
    return {name: fused(name) for name in own if calls[name]}


def hlo_op_names(data: bytes) -> dict:
    """{program id: {instruction name: [op_name, ...]}} (`_fused_op_names`)
    of every program in the trace. A fusion's `tf_op` names its root alone;
    what it fuses is only here."""
    return {ident: _fused_op_names(stats["Hlo Proto"])
            for name, _, events in _planes(data) if name == HLO_PLANE
            for ident, (_, stats) in events.items() if "Hlo Proto" in stats}


def _varints(buf) -> list[int]:
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


# -- device operations -> layers ---------------------------------------------

def parse_scope(op_name: str) -> tuple[str, str] | None:
    """(layer type, layer name) of the innermost layer scope; the
    benchmark's own reading of `utils/spans.py`'s grammar (the tests hold
    the two together)."""
    found = LAYER_SCOPE.findall(op_name)
    return (found[-1][0], unquote(found[-1][1])) if found else None


def scope_key(op_name: str) -> tuple[str, str, str] | None:
    """(layer type, layer name, phase) of one `op_name`; None without a
    scope of the program's."""
    layer = parse_scope(op_name)
    if layer is not None:
        return (*layer, "backward" if "transpose(" in op_name else "forward")
    for scope, phase in SOLVER_SCOPES.items():
        if scope in op_name:
            return ("", scope, phase)
    return None


def classify(tf_op: str, fused=(), root_wins: bool = False
             ) -> tuple[tuple[str, str, str], float]:
    """((layer type, layer name, phase), share of its scoped instructions
    that carry another) of an operation, from its `tf_op` statistic (the
    root's `op_name`s, `a;b:`) and the `op_name`s of what it fuses."""
    root = [scope_key(n) for n in tf_op.rsplit(":", 1)[0].split(";")]
    votes = Counter(k for k in map(scope_key, fused) if k) \
        or Counter(k for k in root if k)
    if not votes:
        return ("", "", "unscoped"), 0.0
    if root_wins and root[0] is not None:
        return root[0], 0.0
    chosen = max(votes, key=votes.get)   # ties: the first
    return chosen, 1.0 - votes[chosen] / sum(votes.values())


def _reduce_device(ops, metadata: dict, hlo: dict) -> dict:
    leaves = [ev for ev in ops if not ev.has_child]
    out = {"busy_s": _total(_union([(e.start, e.end) for e in leaves])) / 1e9,
           "outvoted_s": 0.0}
    for key in ("phase_s", "layer_s", "layer_flops", "layer_bytes",
                "layer_ops", "unscoped_s", "op_s"):
        out[key] = defaultdict(float)
    for ev in leaves:
        meta = metadata.get(ev.name, {})
        fused = hlo.get(meta.get("program_id"), {}).get(ev.short, ())
        key, outvoted = classify(
            meta.get("tf_op", ""), fused,
            root_wins=trace_reduce.category(ev) == "convolution fusion")
        seconds = (ev.end - ev.start) / 1e9
        out["phase_s"][key[2]] += seconds
        out["op_s"][f"{ev.label} | {' '.join(key).strip()}"] += seconds
        out["outvoted_s"] += seconds * outvoted
        if key[2] == "unscoped":
            out["unscoped_s"][ev.label] += seconds
            continue
        out["layer_s"][key] += seconds
        out["layer_flops"][key] += meta.get("flops", 0)
        out["layer_bytes"][key] += meta.get("bytes_accessed", 0)
        out["layer_ops"][key] += 1
    return out


# -- host spans against idle gaps --------------------------------------------

def _host_spans(profile, shift_ns: float) -> list[list]:
    """The program's spans on the device clock, one list per host thread,
    nested (`child_ns` charged)."""
    threads = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [ev for ev in _events(line)
                     if ev.name.startswith(SPAN_PREFIX)]
            for ev in spans:
                ev.start -= shift_ns
                ev.end -= shift_ns
            _nest(spans)
            if spans:
                threads.append(spans)
    return threads


def _timeline(spans: list) -> list[tuple[float, float, str]]:
    """Consecutive (start, end, label) segments, each labelled by the
    innermost `caffe/solver/*` span open in it: the one that began last."""
    spans = sorted((ev for ev in spans if ev.name.startswith(SOLVER_PREFIX)),
                   key=lambda ev: (ev.start, -(ev.end - ev.start)))
    times = sorted({t for ev in spans for t in (ev.start, ev.end)})
    out, open_, nxt = [], [], 0
    for t0, t1 in zip(times, times[1:]):
        while nxt < len(spans) and spans[nxt].start <= t0:
            open_.append(spans[nxt])
            nxt += 1
        open_ = [ev for ev in open_ if ev.end > t0]
        if open_:
            inner = max(open_, key=lambda ev: (ev.start, ev.start - ev.end))
            out.append((t0, t1, inner.name[len(SOLVER_PREFIX):]))
    return out


def _gaps(ops, modules, timeline, lo: float, hi: float) -> dict:
    """The device's between-programs idle time within [lo, hi], by place
    and by the span that held it; seconds."""
    leaves = [ev for ev in ops if not ev.has_child]
    busy = _union([(ev.start, ev.end) for ev in leaves])
    starts = [m.start for m in modules]
    leaf_starts = [ev.start for ev in leaves]
    segment_starts = [seg[0] for seg in timeline]
    out = {"by_span_s": defaultdict(float), "by_place_s": defaultdict(float)}
    edge = lo
    for s, e in busy + [(hi, hi)]:
        if s > edge:
            where = _position(edge, s, modules, starts, leaves, leaf_starts)
            if where.startswith("between programs"):
                for label, ns in _blame(timeline, segment_starts, edge, s):
                    label = OUTSIDE if label == NO_SPAN else label
                    out["by_span_s"][label] += ns / 1e9
                    out["by_place_s"][f"{where} | {label}"] += ns / 1e9
        edge = max(edge, e)
    return out


# -- the whole trace ---------------------------------------------------------

def reduce_bytes(data: bytes, shift_s: float | None = None) -> dict:
    """Both tables of a serialized xplane. `shift_s` is the host clock's
    lead over the device's (`trace_reduce`'s `host_clock_shift_s`); None
    computes it with `trace_reduce`."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(data)
    if shift_s is None:
        shift_s = (trace_reduce.reduce_profile(profile) or {}).get(
            "host_clock_shift_s")
    threads = _host_spans(profile, 1e9 * (shift_s or 0.0))
    spans = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    for ev in (ev for thread in threads for ev in thread):
        row = spans[ev.name[len(SPAN_PREFIX):]]
        row["count"] += 1
        row["total_s"] += (ev.end - ev.start) / 1e9
        row["self_s"] += ev.own_ns / 1e9
    out = {"host_clock_shift_s": shift_s, "spans": dict(spans),
           "n_devices": 0}

    metadata, hlo = event_metadata(data), hlo_op_names(data)
    planes = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (OPS_LINE, MODULES_LINE)}
        if lines.get(OPS_LINE):
            _nest(lines[OPS_LINE])
            for m in lines.get(MODULES_LINE, []):
                m.name = _module_name(m.name)
            planes.append((plane.name, lines))
    if not planes:
        return out
    timeline = _timeline([ev for thread in threads for ev in thread])
    ops = [ev for _, lines in planes for ev in lines[OPS_LINE]]
    lo, hi = min(ev.start for ev in ops), max(ev.end for ev in ops)
    devices = []
    for name, lines in planes:
        device = _reduce_device(
            lines[OPS_LINE], metadata.get(name, {}).get("events", {}), hlo)
        device["idle"] = _gaps(lines[OPS_LINE], lines.get(MODULES_LINE, []),
                               timeline, lo, hi)
        devices.append(device)
    mean = _mean(devices)
    top = lambda table: dict(sorted(table.items(),
                                    key=lambda kv: -kv[1])[:TOP_N])
    layers = [dict(zip(("type", "name", "phase"), row),
                   seconds=seconds, flops=mean["layer_flops"][row],
                   bytes=mean["layer_bytes"][row],
                   ops=mean["layer_ops"][row])
              for row, seconds in sorted(mean["layer_s"].items(),
                                         key=lambda kv: -kv[1])]
    kind = next(iter(metadata.values()), {}).get("stats", {}).get(
        "device_type_string")
    out.update(
        n_devices=len(devices), device_kind=kind, busy_s=mean["busy_s"],
        phase_s={p: mean["phase_s"].get(p, 0.0) for p in PHASES},
        scoped=any(row["phase"] in ("forward", "backward")
                   for row in layers),
        outvoted_share=mean["outvoted_s"] / mean["busy_s"],
        layers=layers, device_ops=top(mean["op_s"]),
        unscoped_ops=top(mean["unscoped_s"]),
        between_programs_idle_s=sum(mean["idle"]["by_span_s"].values()),
        idle_by_span_s=dict(mean["idle"]["by_span_s"]),
        idle_by_place_s=top(mean["idle"]["by_place_s"]))
    return out


@functools.lru_cache(maxsize=2)
def reduce_xplane(path: str, shift_s: float | None = None) -> dict:
    return reduce_bytes(Path(path).read_bytes(), shift_s)


def for_run(run: dict, trace: dict | None) -> dict | None:
    """What a reader in `layer_metrics/` starts from: the reduction of the
    traced run's xplane, parsed once per process, or None when the run was
    not traced on a TPU (`trace` is None in a CPU rehearsal)."""
    if trace is None or not run.get("traced_iters"):
        return None
    found = sorted((BENCH.parent / "chiprun_out" / "bench" / run["cell"]
                    / "trace").glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        return None
    return reduce_xplane(str(found[-1]), trace.get("host_clock_shift_s"))


def layer_ms_per_step(run: dict, trace: dict | None, keep):
    """Device time a traced iteration spends in the rows of the layer
    table that `keep(row)` holds; None where no operation of the trace
    carries such a scope (a program without scopes)."""
    spans = for_run(run, trace)
    rows = [r for r in (spans or {}).get("layers", []) if keep(r)]
    if not rows:
        return None
    return 1e3 * sum(r["seconds"] for r in rows) / run["traced_iters"]


def span_ms_per_iter(run: dict, trace: dict | None, span: str,
                     table: str = "spans"):
    """Host self time (`table="spans"`) or between-programs device idle
    time (`table="idle_by_span_s"`) of one `caffe/solver/*` span, per
    traced iteration; None where the program wrote no such spans."""
    spans = for_run(run, trace)
    if spans is None or "solver/iter" not in spans["spans"]:
        return None
    if table == "spans":
        seconds = spans["spans"].get("solver/" + span, {}).get("self_s", 0.0)
    else:
        seconds = spans.get(table, {}).get(span, 0.0)
    return 1e3 * seconds / run["traced_iters"]


def roofline(layers: list[dict], peaks: dict) -> None:
    """Add `roofline_share` and what bounds it to each row, in place."""
    for row in layers:
        compute = row["flops"] / peaks["bf16_flops_per_s"]
        memory = row["bytes"] / peaks["hbm_bytes_per_s"]
        row["bound"] = "flops" if compute > memory else "bytes"
        row["roofline_share"] = (max(compute, memory) / row["seconds"]
                                 if row["seconds"] else None)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__)
        return 2
    out = reduce_xplane(argv[0])
    peaks = {kind.lower(): {k: v["value"] for k, v in entry.items()}
             for kind, entry in
             json.loads((BENCH / "peaks.json").read_text()).items()}
    kind = (out.get("device_kind") or "").lower()
    if kind in peaks:
        roofline(out["layers"], peaks[kind])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
