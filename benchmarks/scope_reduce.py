"""Profiler trace (xplane) -> device time under one named scope of the
program's, whatever implements the work under it.

`span_reduce.py` files every device operation under a prototxt layer
(`caffe.<Type>.<name>`). Inside a layer the program may open further
scopes (`caffe_mpi_tpu/utils/spans.py`: `moe.route`, `moe.dispatch`,
`moe.experts`, `moe.combine`); this file sums the leaf operations that
belong to one of them, by the same rule `span_reduce` uses for layers: an
operation goes to the scope most of its instructions' `op_name`s carry (a
fusion's own instructions are read from the program's HLO, which the
profiler keeps in the trace; a bare operation has its `tf_op` alone).
Forward and backward both count: `jvp(...)` and `transpose(jvp(...))` wrap
the scope's name, they do not replace it.

Per device means, as in `trace_reduce`. A trace of a program without the
scope gives None.
"""

from __future__ import annotations

import functools
from pathlib import Path

import span_reduce
from trace_reduce import DEVICE_PLANE, OPS_LINE, _events, _nest


def _carries(needle: str, tf_op: str, fused) -> bool:
    names = [n for n in fused if n] \
        or [n for n in tf_op.rsplit(":", 1)[0].split(";") if n]
    return bool(names) and 2 * sum(needle in n for n in names) > len(names)


@functools.lru_cache(maxsize=8)
def scope_seconds(path: str, needle: str) -> float | None:
    """Mean over devices of the summed durations of the leaf operations
    under the scope `needle`; None where no operation carries it."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    profile = ProfileData.from_serialized_xspace(data)
    metadata = span_reduce.event_metadata(data)
    hlo = span_reduce.hlo_op_names(data)
    totals, found = [], False
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line)]
        if not ops:
            continue
        _nest(ops)
        events = metadata.get(plane.name, {}).get("events", {})
        total = 0.0
        for ev in ops:
            if ev.has_child:
                continue
            meta = events.get(ev.name, {})
            fused = hlo.get(meta.get("program_id"), {}).get(ev.short, ())
            if _carries(needle, meta.get("tf_op", ""), fused):
                total += (ev.end - ev.start) / 1e9
                found = True
        totals.append(total)
    return sum(totals) / len(totals) if found else None


def for_run(run: dict, trace: dict | None, needle: str) -> float | None:
    """`scope_seconds` of the traced run's xplane; None when the run was
    not traced on a TPU."""
    if trace is None or not run.get("traced_iters"):
        return None
    found = sorted((span_reduce.BENCH.parent / "chiprun_out" / "bench"
                    / run["cell"] / "trace").glob(
                        "plugins/profile/*/*.xplane.pb"))
    return scope_seconds(str(found[-1]), needle) if found else None
