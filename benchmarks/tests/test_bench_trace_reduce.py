"""The trace reducer: hand-made cases with hand-computed answers, and the
trace recorded on a four-chip host by record_fixture.py."""

from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

import trace_reduce

FIXTURE = Path(__file__).with_name("toy_dp4.xplane.pb")

CONV = ('%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} %a), '
        'kind=kOutput, calls=%fc.1')
LOOP = '%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %b), kind=kLoop'
WHILE = '%while.3 = (s32[], f32[8,8]{1,0}) while((s32[], f32[8,8]{1,0}) %t)'
MOSAIC = ('%lrn_fwd.4 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %c), '
          'custom_call_target="tpu_custom_call"')
CONCAT = ('%custom-call.5 = f32[8,8]{1,0} custom-call(f32[8,4]{1,0} %d), '
          'custom_call_target="ConcatBitcast"')
AR_START = ('%all-reduce-start.6 = f32[8,8]{1,0} all-reduce-start('
            'f32[8,8]{1,0} %g), replica_groups={{0,1}}')
AR_DONE = ('%all-reduce-done.6 = f32[8,8]{1,0} all-reduce-done('
           'f32[8,8]{1,0} %all-reduce-start.6)')


def _plane(i: int, name: str, lines: dict) -> str:
    """One XPlane in text-proto form from {line: [(event, start_ns,
    duration_ns[, run_id]), ...]}."""
    ids: dict[str, int] = {}
    body = ""
    for j, (line, events) in enumerate(lines.items()):
        body += f'lines {{ id: {j + 1} name: "{line}" timestamp_ns: 0\n'
        for event, start, duration, *run_id in events:
            key = ids.setdefault(event, len(ids) + 1)
            stat = (f"stats {{ metadata_id: 1 int64_value: {run_id[0]} }} "
                    if run_id else "")
            body += (f"events {{ metadata_id: {key} offset_ps: "
                     f"{start * 1000} duration_ps: {duration * 1000} "
                     f"{stat}}}\n")
        body += "}\n"
    quote = lambda text: text.replace("\\", "\\\\").replace('"', '\\"')
    meta = "".join(f'event_metadata {{ key: {key} value {{ id: {key} '
                   f'name: "{quote(event)}" }} }}\n'
                   for event, key in ids.items())
    meta += 'stat_metadata { key: 1 value { id: 1 name: "run_id" } }\n'
    return f'planes {{ id: {i} name: "{name}"\n{body}{meta}}}\n'


def profile(device_lines: dict, host_events=()) -> ProfileData:
    """A trace with one device plane and the host's python thread."""
    return ProfileData.from_text_proto(
        _plane(1, "/device:TPU:0", device_lines)
        + _plane(2, "/host:CPU", {"python3": list(host_events)}))


def test_busy_union_idle_share_and_own_time():
    # a wrapper 0..100 holds two leaves 10..40 and 30..60 (overlapping); a
    # third leaf runs 120..150. The window is the operations' extent, 0..150,
    # and busy is [10,60] + [120,150] = 80 of it.
    p = profile({"XLA Ops": [(WHILE, 0, 100), (CONV, 10, 30), (LOOP, 30, 30),
                             (LOOP, 120, 30)]})
    s = trace_reduce.reduce_profile(p)
    assert s["window_s"] == pytest.approx(150e-9)
    assert s["busy_s"] == pytest.approx(80e-9)
    assert s["idle_share"] == pytest.approx(1 - 80 / 150)
    # the wrapper's own time is its 100 less its children's 60
    assert s["by_category_s"]["other"] == pytest.approx(40e-9)
    assert s["by_category_s"]["convolution fusion"] == pytest.approx(30e-9)
    assert s["by_category_s"]["other fusion"] == pytest.approx(60e-9)
    ops = dict(s["device_ops"])
    assert ops["fusion.2 [fusion/kLoop] f32[8,8]"] == pytest.approx(60e-9)
    assert sum(dict(s["idle_gaps"]).values()) == pytest.approx(70e-9)


def test_only_mosaic_custom_calls_count_as_kernels():
    p = profile({"XLA Ops": [(MOSAIC, 0, 10), (CONCAT, 10, 5),
                             (MOSAIC, 20, 10)]})
    s = trace_reduce.reduce_profile(p)
    assert s["custom_calls"] == {
        "lrn_fwd": {"count": 2, "seconds": pytest.approx(20e-9)}}
    assert s["by_category_s"]["tpu_custom_call"] == pytest.approx(20e-9)
    assert s["by_category_s"]["copy/transpose"] == pytest.approx(5e-9)


def test_a_gap_is_split_among_the_host_spans_open_while_it_lasts():
    # one gap inside a program (10..15) and one between programs (20..50)
    # that begins while the host still waits in solver.step, lasts through
    # the benchmark's own pause and ends after the next step was entered
    p = profile(
        {"XLA Ops": [(CONV, 0, 10), (LOOP, 15, 5), (CONV, 50, 10)],
         "XLA Modules": [("jit_step(123)", 0, 20), ("jit_step(123)", 50, 10)]},
        [("bench/outer", 0, 60), ("bench/solver.step", 0, 22),
         ("bench/between_blocks", 22, 20), ("bench/solver.step", 42, 18)])
    s = trace_reduce.reduce_profile(p)
    assert s["host_clock_shift_s"] is None     # nothing to align by
    assert dict(s["idle_gaps"]) == {
        "inside solver.step | within jit_step: fusion.1 -> fusion.2":
            pytest.approx(5e-9),
        "inside solver.step | between programs: jit_step -> jit_step":
            pytest.approx(10e-9),               # 20..22 and 42..50
        "between_blocks | between programs: jit_step -> jit_step":
            pytest.approx(20e-9)}


def test_host_spans_are_moved_onto_the_device_clock():
    # the host enqueued run 7 at 100 on its clock and the device shows it
    # starting at 40 on its own: the host's clock runs 60 ahead. (Run 8 waited
    # in the queue, so it bounds the shift less tightly.) Shifted by 60, the
    # host's pause 130..160 is the device's gap 70..100.
    p = ProfileData.from_text_proto(
        _plane(1, "/device:TPU:0", {
            "XLA Ops": [(CONV, 40, 30), (CONV, 100, 10)],
            "XLA Modules": [("jit_step(1)", 40, 30, 7),
                            ("jit_step(1)", 100, 10, 8)]})
        + _plane(2, "/host:CPU", {"python3": [
            ("bench/solver.step", 90, 40), ("bench/pause", 130, 30),
            ("DoEnqueueProgram", 100, 5, 7),
            ("DoEnqueueProgram", 120, 5, 8)]}))
    s = trace_reduce.reduce_profile(p)
    assert s["host_clock_shift_s"] == pytest.approx(60e-9)
    assert dict(s["idle_gaps"]) == {
        "pause | between programs: jit_step -> jit_step":
            pytest.approx(30e-9)}


def test_allreduce_exposed_is_the_part_no_compute_covers():
    # the collective runs 10..50 (async line); compute covers 0..30 of it,
    # so 20 of its 40 are exposed: the done op's wait 30..50
    p = profile({
        "XLA Ops": [(CONV, 0, 10), (AR_START, 10, 1), (LOOP, 11, 19),
                    (AR_DONE, 30, 20), (LOOP, 50, 10)],
        "Async XLA Ops": [(AR_START, 10, 40)]})
    ar = trace_reduce.reduce_profile(p)["allreduce"]
    assert ar["count"] == 1
    assert ar["seconds"] == pytest.approx(40e-9)
    assert ar["exposed_seconds"] == pytest.approx(21e-9)  # + the start op


def test_a_cpu_trace_has_nothing_to_reduce():
    host_only = ProfileData.from_text_proto(
        _plane(1, "/host:CPU", {"python3": [("bench/solver.step", 0, 10)]}))
    assert trace_reduce.reduce_profile(host_only) is None
    with pytest.raises(ValueError, match="XLA Ops"):
        trace_reduce.reduce_profile(profile({}))


@pytest.fixture(scope="module")
def recorded():
    return (ProfileData.from_file(str(FIXTURE)),
            trace_reduce.reduce_xplane(str(FIXTURE)))


def test_recorded_trace_busy_union_against_a_raster(recorded):
    """Busy time on each chip, recomputed by painting every leaf operation
    onto a 1 ns raster of the window."""
    profile, summary = recorded
    assert summary["n_devices"] == 4
    planes = {p.name: p for p in profile.planes}
    starts, ends = [], []
    per_plane = {}
    for name in summary["busy_s_per_device"]:
        ops = next(line for line in planes[name].lines
                   if line.name == "XLA Ops")
        events = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                  for e in ops.events]
        per_plane[name] = events
        starts.append(min(s for s, _ in events))
        ends.append(max(e for _, e in events))
    lo, hi = min(starts), max(ends)
    assert summary["window_s"] == pytest.approx((hi - lo) / 1e9, rel=1e-6)
    for name, events in per_plane.items():
        # a wrapper (the loop) encloses its body's operations: drop any
        # event that strictly contains another
        leaves = [(s, e) for s, e in events if not any(
            (s <= s2 and e2 <= e) and (s2, e2) != (s, e)
            for s2, e2 in events)]
        raster = np.zeros(hi - lo, bool)
        for s, e in leaves:
            raster[s - lo:e - lo] = True
        assert summary["busy_s_per_device"][name] == pytest.approx(
            raster.sum() / 1e9, rel=1e-3)


def test_recorded_trace_counts_and_blame(recorded):
    _, s = recorded
    # three steps on every chip: one program, one gradient all-reduce (all of
    # it exposed: it is a synchronous operation) and one Mosaic LRN kernel each
    assert s["programs"]["jit_step"]["count"] == 3
    assert s["allreduce"]["count"] == 3
    assert s["allreduce"]["exposed_seconds"] == pytest.approx(
        s["allreduce"]["seconds"])
    assert s["by_category_s"]["all-reduce"] == pytest.approx(
        s["allreduce"]["seconds"])
    (kernel, calls), = s["custom_calls"].items()
    assert calls["count"] == 3 and calls["seconds"] > 0
    assert s["by_category_s"]["tpu_custom_call"] == pytest.approx(
        calls["seconds"])
    # the host slept 3 ms after each step; two of the sleeps lie between
    # device programs, and the larger part of that idle time is theirs
    assert 0 < s["host_clock_shift_s"] < 0.01
    gaps = dict(s["idle_gaps"])
    between = "between programs: jit_step -> jit_step"
    assert gaps[f"between_blocks | {between}"] > 0.006
    assert gaps[f"between_blocks | {between}"] > \
        2 * gaps[f"inside solver.step | {between}"]
    assert sum(gaps.values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-2)
