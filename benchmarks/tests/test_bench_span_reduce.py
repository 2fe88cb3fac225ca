"""The span reducer: hand-made traces with hand-computed answers, the trace
recorded on one chip through the program's own `Solver` by
record_scoped_fixture.py (scopes and spans), and the four-chip trace of
record_fixture.py (neither: a program that writes none)."""

import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import span_reduce
from conftest import BENCH

SCOPED = Path(__file__).with_name("toy_scoped.xplane.pb")
UNSCOPED = Path(__file__).with_name("toy_dp4.xplane.pb")

FUSION = "%fusion.{} = f32[8,8]{{1,0}} fusion(f32[8,8]{{1,0}} %a), kind=kLoop"
WHILE = "%while.9 = (s32[], f32[8,8]{1,0}) while((s32[], f32[8,8]{1,0}) %t)"


def message(*fields) -> bytes:
    """Protobuf wire format of (field number, int | str | bytes) pairs."""
    def varint(n):
        out = b""
        while n > 0x7F:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def hlo_proto(fusions: dict) -> bytes:
    """An HloProto whose entry computation holds one fusion instruction
    per entry of `fusions` ({instruction name: (its own op_name, [op_names
    of the instructions it fuses])})."""
    computations, entry = [], []
    for ident, (name, (own, fused)) in enumerate(fusions.items(), 1):
        body = [message((1, f"{name}.i{j}"), (7, message((2, op_name))))
                for j, op_name in enumerate(fused)]
        body.append(message((1, f"{name}.param")))        # no metadata
        computations.append(message((1, f"fused_{name}"), (5, ident),
                                    *[(2, i) for i in body]))
        entry.append(message((1, name), (7, message((2, own))),
                             (38, ident)))
    computations.append(message((1, "main"), (5, 99),
                                *[(2, i) for i in entry]))
    return message((1, message((1, "jit_step"),
                               *[(3, c) for c in computations])))


def xspace(device_ops, host_events=(), modules=(), hlo: bytes = b"") -> bytes:
    """A serialized trace with one device plane and the host's python
    thread. `device_ops`: [(event name, tf_op or None, flops, bytes, start_ns,
    duration_ns)]; `host_events` and `modules`: [(name, start_ns,
    duration_ns)]; `hlo`: the HloProto of program 7, which every device
    operation then belongs to."""
    quote = lambda text: text.replace("\\", "\\\\").replace('"', '\\"')

    def line(j, name, events, ids):
        body = f'lines {{ id: {j} name: "{name}" timestamp_ns: 0\n'
        for event, start, duration in events:
            key = ids.setdefault(event, len(ids) + 1)
            body += (f"events {{ metadata_id: {key} offset_ps: "
                     f"{start * 1000} duration_ps: {duration * 1000} }}\n")
        return body + "}\n"

    ids, stats = {}, {}
    for name, tf_op, flops, nbytes, *_ in device_ops:
        stats[name] = (tf_op, flops, nbytes)
    device = line(1, "XLA Ops", [(n, s, d) for n, *_, s, d in device_ops],
                  ids)
    device += line(2, "XLA Modules", modules, ids)
    meta = ""
    for event, key in ids.items():
        tf_op, flops, nbytes = stats.get(event, (None, 0, 0))
        extra = (f'stats {{ metadata_id: 1 str_value: "{quote(tf_op)}" }} '
                 if tf_op is not None else "")
        meta += (f'event_metadata {{ key: {key} value {{ id: {key} name: '
                 f'"{quote(event)}" {extra}'
                 f'stats {{ metadata_id: 2 uint64_value: {flops} }} '
                 f'stats {{ metadata_id: 3 uint64_value: {nbytes} }} '
                 f'stats {{ metadata_id: 4 uint64_value: 7 }} }} }}\n')
    for key, name in enumerate(("tf_op", "flops", "bytes_accessed",
                                "program_id"), 1):
        meta += (f'stat_metadata {{ key: {key} value {{ id: {key} name: '
                 f'"{name}" }} }}\n')
    host_ids = {}
    host = line(1, "python3", list(host_events), host_ids)
    host += "".join(f'event_metadata {{ key: {key} value {{ id: {key} name: '
                    f'"{quote(event)}" }} }}\n'
                    for event, key in host_ids.items())
    programs = ""
    if hlo:
        octal = "".join(f"\\{byte:03o}" for byte in hlo)
        programs = (
            'planes { id: 3 name: "/host:metadata"\n'
            'event_metadata { key: 7 value { id: 7 name: "jit_step(7)" '
            f'stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }}\n'
            'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }\n}\n')
    return ProfileData.text_proto_to_serialized_xspace(
        f'planes {{ id: 1 name: "/device:TPU:0"\n{device}{meta}}}\n'
        f'planes {{ id: 2 name: "/host:CPU"\n{host}}}\n{programs}')


# -- the grammar and the rule for fusions ------------------------------------

CONV1_B = "jit(step)/transpose(jvp(caffe.Convolution.conv1))/reduce_sum"
RELU1_B = "jit(step)/transpose(jvp(caffe.ReLU.relu1))/select_n"
LRN1_B = "jit(step)/transpose(jvp(caffe.LRN.norm1))/mul"
LRN1_F = "jit(step)/jvp(caffe.LRN.norm1)/mul"
UPDATE = "jit(step)/solver.update/sub"


@pytest.mark.parametrize("tf_op,fused,root_wins,key,outvoted", [
    ("jit(step)/jvp(caffe.Convolution.conv1)/conv_general_dilated:", (),
     False, ("Convolution", "conv1", "forward"), 0),
    ("jit(step)/transpose(jvp(caffe.LRN.norm1))/cond/branch_0_fun/lrn_bwd/"
     "pallas_call:", (), False, ("LRN", "norm1", "backward"), 0),
    # a name that holds `/` comes back whole
    ("jit(step)/jvp(caffe.Convolution.inception_3a%2F1x1)/add:", (), False,
     ("Convolution", "inception_3a/1x1", "forward"), 0),
    # nested layer scopes: the innermost wins
    ("jit(f)/jvp(caffe.Pipeline.trunk)/while/body/jvp(caffe.LayerNorm.ln)"
     "/mul:", (), False, ("LayerNorm", "ln", "forward"), 0),
    # serving's un-differentiated pass is forward
    ("jit(forward)/caffe.InnerProduct.fc8/dot_general:", (), False,
     ("InnerProduct", "fc8", "forward"), 0),
    # a loop fusion named after conv1's bias gradient that holds ReLU's and
    # LRN's backward pass goes where most of its instructions are
    (CONV1_B + ":", [CONV1_B, RELU1_B, LRN1_B, LRN1_B, LRN1_B, LRN1_F, ""],
     False, ("LRN", "norm1", "backward"), 3 / 6),
    # a convolution fusion is its convolution, whatever the epilogue holds
    (CONV1_B + ":", [CONV1_B, UPDATE, UPDATE, UPDATE], True,
     ("Convolution", "conv1", "backward"), 0),
    # no root name (a compiler-made fusion): what it fuses decides
    ("", ["jit(step)/jvp(caffe.ReLU.relu3)/max",
          "jit(step)/jvp(caffe.ReLU.relu3)/eq", ""], False,
     ("ReLU", "relu3", "forward"), 0),
    # ties go to the first
    (f"{UPDATE};{RELU1_B};{LRN1_F}:", (), False,
     ("", "solver.update", "update"), 2 / 3),
    ("jit(step)/shard_map/solver.reduce/psum:", (), False,
     ("", "solver.reduce", "reduce"), 0),
    ("jit(step)/jvp()/while/body/dot_general:", ["", ""], False,
     ("", "", "unscoped"), 0),
    ("broadcast.1:", (), True, ("", "", "unscoped"), 0),
    ("", (), False, ("", "", "unscoped"), 0),
])
def test_classify(tf_op, fused, root_wins, key, outvoted):
    got, share = span_reduce.classify(tf_op, fused, root_wins)
    assert got == key and share == pytest.approx(outvoted)


def test_grammar_is_the_programs():
    """The benchmark keeps its own reading of the scope grammar (it must
    run against commits whose program lacks `utils/spans.py`); here the
    two are held together."""
    from caffe_mpi_tpu.utils import spans
    for type_, name in [("Convolution", "conv1"), ("ReLU", "a/b/c"),
                        ("Python", "odd name (v2);x:y%z"), ("LRN", "n.1-b~")]:
        scope = spans.scope_name(type_, name)
        for text in (f"jit(step)/jvp({scope})/mul",
                     f"jit(step)/transpose(jvp({scope}))/while/body/add"):
            assert span_reduce.parse_scope(text) == (type_, name)
            assert spans.parse_scope(text) == (type_, name)
    assert span_reduce.LAYER_SCOPE.pattern == spans._SCOPE.pattern


# -- hand-made traces --------------------------------------------------------

def hand_made() -> dict:
    conv = "jit(step)/jvp(caffe.Convolution.conv1)/conv:"
    conv_b = "jit(step)/transpose(jvp(caffe.Convolution.conv1))/conv:"
    ops = [
        # program 1 runs 0..100: a while 10..70 holding two leaves, then
        # the update 80..100
        (WHILE, None, 0, 0, 10, 60),
        (FUSION.format(1), conv, 2000, 100, 10, 20),
        (FUSION.format(2), conv_b, 4000, 300, 40, 30),
        (FUSION.format(3), "jit(step)/solver.update/sub:", 10, 40, 80, 20),
        # between programs: an eager copy without metadata, 150..160
        ("%copy.4 = f32[8,8]{1,0} copy(f32[8,8]{1,0} %b)", None, 0, 64,
         150, 10),
        # program 2 runs 200..260: conv1 forward again, and a fusion of two
        # layers
        (FUSION.format(1), conv, 2000, 100, 200, 20),
        (FUSION.format(5), "jit(step)/jvp(caffe.ReLU.relu1)/max:", 0, 200,
         230, 30),
    ]
    # what the program's HLO says the fusions hold: fusion.5, named after
    # ReLU, is one part ReLU and two parts LRN
    hlo = hlo_proto({"fusion.5": ("jit(step)/jvp(caffe.ReLU.relu1)/max", [
        "jit(step)/jvp(caffe.ReLU.relu1)/max", LRN1_F, LRN1_F, LRN1_F])})
    modules = [("jit_step(1)", 0, 100), ("jit_copy(2)", 150, 10),
               ("jit_step(3)", 200, 60)]
    host = [
        ("caffe/solver/iter", 0, 140),
        ("caffe/solver/feed wait", 5, 15),
        ("caffe/solver/train dispatch", 20, 100),     # ends at 120
        ("caffe/solver/iter", 140, 100),              # 140..240
        ("caffe/solver/feed wait", 145, 25),          # 145..170
        ("caffe/solver/train dispatch", 170, 20),     # 170..190
        ("bench/solver.step", 0, 300),
    ]
    return span_reduce.reduce_bytes(xspace(ops, host, modules, hlo),
                                    shift_s=0.0)


def test_layers_phases_and_busy_time():
    out = hand_made()
    ns = lambda s: round(1e9 * s)
    assert ns(out["busy_s"]) == 20 + 30 + 20 + 10 + 20 + 30
    assert {p: ns(s) for p, s in out["phase_s"].items()} == {
        "forward": 20 + 20 + 30, "backward": 30, "update": 20, "reduce": 0,
        "unscoped": 10}
    assert sum(out["phase_s"].values()) == pytest.approx(out["busy_s"])
    rows = {(r["type"], r["name"], r["phase"]): r for r in out["layers"]}
    conv = rows["Convolution", "conv1", "forward"]
    assert (ns(conv["seconds"]), conv["flops"], conv["bytes"],
            conv["ops"]) == (40, 4000, 200, 2)
    assert ns(rows["Convolution", "conv1", "backward"]["seconds"]) == 30
    assert ns(rows["LRN", "norm1", "forward"]["seconds"]) == 30
    assert ("ReLU", "relu1", "forward") not in rows
    assert ns(rows["", "solver.update", "update"]["seconds"]) == 20
    # 1 of fusion.5's 4 scoped instructions is ReLU's
    assert out["outvoted_share"] == pytest.approx(30 * 1 / 4 / 130)
    assert ns(out["device_ops"][
        "fusion.5 [fusion/kLoop] f32[8,8] | LRN norm1 forward"]) == 30
    assert [ns(s) for s in out["unscoped_ops"].values()] == [10]
    assert out["scoped"] is True
    # layers sum to busy less the unscoped part
    assert sum(r["seconds"] for r in out["layers"]) == pytest.approx(
        out["busy_s"] - out["phase_s"]["unscoped"])


def test_span_self_time_and_gap_blame():
    out = hand_made()
    ns = lambda s: round(1e9 * s)
    spans = out["spans"]
    assert set(spans) == {"solver/iter", "solver/feed wait",
                          "solver/train dispatch"}     # bench/ is not ours
    assert spans["solver/iter"]["count"] == 2
    assert ns(spans["solver/iter"]["total_s"]) == 240
    assert ns(spans["solver/iter"]["self_s"]) == 240 - 15 - 100 - 25 - 20
    assert ns(spans["solver/feed wait"]["self_s"]) == 40
    # idle between programs: 100..150 (train dispatch to 120, iter to 145,
    # feed wait to 150) and 160..200 (feed wait to 170, train dispatch to
    # 190, iter to 200); 0..10 and 70..80 lie within program 1
    assert ns(out["between_programs_idle_s"]) == 50 + 40
    assert {k: ns(v) for k, v in out["idle_by_span_s"].items()} == {
        "train dispatch": 20 + 20, "iter": 25 + 10, "feed wait": 5 + 10}
    assert sum(out["idle_by_span_s"].values()) == pytest.approx(
        out["between_programs_idle_s"])
    assert ns(out["idle_by_place_s"][
        "between programs: jit_copy -> jit_step | train dispatch"]) == 20


def test_spans_outside_any_step_and_host_shift():
    ops = [(FUSION.format(1), None, 0, 0, 1000, 10),
           (FUSION.format(2), None, 0, 0, 1100, 10)]
    modules = [("jit_a(1)", 1000, 10), ("jit_b(2)", 1100, 10)]
    # the host clock leads by 1000 ns: on the device clock the span covers
    # 1030..1060 of the gap 1010..1100
    host = [("caffe/solver/display sync", 2030, 30)]
    out = span_reduce.reduce_bytes(xspace(ops, host, modules), shift_s=1e-6)
    ns = lambda s: round(1e9 * s)
    assert {k: ns(v) for k, v in out["idle_by_span_s"].items()} == {
        "display sync": 30, span_reduce.OUTSIDE: 60}


def test_roofline_share():
    rows = [{"flops": 197e12 * 1e-3, "bytes": 819e9 * 4e-3, "seconds": 8e-3},
            {"flops": 197e12 * 2e-3, "bytes": 0, "seconds": 4e-3}]
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    span_reduce.roofline(rows, {k: v["value"] for k, v in peaks.items()})
    assert [(r["bound"], round(r["roofline_share"], 6)) for r in rows] == [
        ("bytes", 0.5), ("flops", 0.5)]


# -- recorded traces ---------------------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    return span_reduce.reduce_xplane(str(SCOPED))


def test_recorded_layers_partition_busy_time(scoped):
    assert scoped["n_devices"] == 1 and scoped["scoped"]
    total = sum(scoped["phase_s"].values())
    assert total == pytest.approx(scoped["busy_s"], rel=1e-6)
    assert sum(r["seconds"] for r in scoped["layers"]) == pytest.approx(
        scoped["busy_s"] - scoped["phase_s"]["unscoped"], rel=1e-6)
    for phase in ("forward", "backward", "update"):
        assert scoped["phase_s"][phase] > 0
    assert scoped["phase_s"]["reduce"] == 0       # one chip
    assert 0 < scoped["phase_s"]["unscoped"] < 0.5 * scoped["busy_s"]


def test_recorded_layers_are_the_prototxts(scoped):
    seen = {}
    for r in scoped["layers"]:
        seen.setdefault((r["type"], r["name"]), set()).add(r["phase"])
    both = {"forward", "backward"}
    assert seen["Convolution", "conv1"] == both
    assert seen["InnerProduct", "block/fc"] == both    # a `/` in the name
    assert seen["InnerProduct", "fc2"] == both
    assert seen["LRN", "norm1"] == both
    assert seen["", "solver.update"] == {"update"}
    assert seen["Input", "data"] == {"forward"}     # the cast to bf16
    assert {t for t, _ in seen} <= {"Input", "Convolution", "ReLU", "LRN",
                                    "Pooling", "InnerProduct",
                                    "SoftmaxWithLoss", ""}
    conv = [r for r in scoped["layers"] if r["name"] == "conv1"]
    assert all(r["flops"] > 0 and r["bytes"] > 0 for r in conv)


def test_recorded_kernels_carry_their_own_names():
    """The Pallas LRN kernels trace as `lrn_fwd` / `lrn_bwd` and resolve
    to the LRN layer's forward and backward."""
    data = SCOPED.read_bytes()
    (plane,) = span_reduce.event_metadata(data).values()
    kernels = {name.split(" ")[0].lstrip("%").split(".")[0]:
               span_reduce.scope_key(stats["tf_op"])
               for name, stats in plane["events"].items()
               if 'custom_call_target="tpu_custom_call"' in name}
    assert kernels == {"lrn_fwd": ("LRN", "norm1", "forward"),
                       "lrn_bwd": ("LRN", "norm1", "backward")}
    assert plane["stats"]["device_type_string"].lower() == "tpu v5 lite"


def test_recorded_spans_and_gap_blame(scoped):
    spans = scoped["spans"]
    assert spans["solver/iter"]["count"] == 4
    assert spans["solver/feed wait"]["count"] == 4
    assert spans["solver/train dispatch"]["count"] == 4
    assert spans["solver/display sync"]["count"] == 1
    nested = sum(spans[k]["total_s"] for k in spans
                 if k not in ("solver/iter", "solver/guard check"))
    assert spans["solver/iter"]["self_s"] == pytest.approx(
        spans["solver/iter"]["total_s"] - nested, rel=1e-6)
    assert scoped["host_clock_shift_s"] is not None
    assert scoped["between_programs_idle_s"] > 0
    assert sum(scoped["idle_by_span_s"].values()) == pytest.approx(
        scoped["between_programs_idle_s"], rel=1e-9)
    named = sum(v for k, v in scoped["idle_by_span_s"].items()
                if k not in ("iter", span_reduce.OUTSIDE))
    assert named > 0.5 * scoped["between_programs_idle_s"]


def test_busy_time_is_trace_reduces(scoped):
    import trace_reduce
    summary = trace_reduce.reduce_xplane(str(SCOPED))
    assert scoped["busy_s"] == pytest.approx(summary["busy_s"], rel=1e-9)
    assert scoped["host_clock_shift_s"] == summary["host_clock_shift_s"]
    kernels = summary["custom_calls"]
    assert set(kernels) == {"lrn_fwd", "lrn_bwd"}
    assert {k["count"] for k in kernels.values()} == {4}


# -- the readers -------------------------------------------------------------

NEW = ["forward_ms_per_step", "backward_ms_per_step", "update_ms_per_step",
       "lrn_ms_per_step", "unscoped_device_share", "feed_wait_ms_per_iter",
       "dispatch_ms_per_iter", "feed_wait_idle_ms_per_iter",
       "dispatch_idle_ms_per_iter"]


def read(metric: str, run: dict, trace: dict | None):
    import run as harness
    return harness.load_module(
        BENCH / "layer_metrics" / f"{metric}.py").compute(run, trace)


@pytest.fixture
def traced_cell(tmp_path, monkeypatch):
    """A run record whose cell's trace directory holds a given xplane,
    as the `train` driver leaves it."""
    monkeypatch.setattr(span_reduce, "BENCH", tmp_path / "benchmarks")
    span_reduce.reduce_xplane.cache_clear()

    def place(xplane: Path, traced_iters: int):
        import shutil
        import trace_reduce
        target = (tmp_path / "chiprun_out" / "bench" / "toy" / "trace"
                  / "plugins" / "profile" / "2026_01_01")
        target.mkdir(parents=True)
        shutil.copy(xplane, target / "host.xplane.pb")
        return ({"cell": "toy", "traced_iters": traced_iters, "chips": 1},
                trace_reduce.reduce_xplane(str(xplane)))
    return place


def test_readers_on_the_scoped_trace(traced_cell, scoped):
    run, trace = traced_cell(SCOPED, 4)
    values = {m: read(m, run, trace) for m in NEW}
    assert all(v is not None and v >= 0 for v in values.values()), values
    parts = sum(values[m] for m in ("forward_ms_per_step",
                                    "backward_ms_per_step",
                                    "update_ms_per_step"))
    busy_ms = 1e3 * trace["busy_s"] / 4
    assert parts + values["unscoped_device_share"] / 100 * busy_ms \
        == pytest.approx(busy_ms, rel=1e-6)
    assert 0 < values["lrn_ms_per_step"] < parts
    assert values["feed_wait_idle_ms_per_iter"] \
        + values["dispatch_idle_ms_per_iter"] \
        <= 1e3 * scoped["between_programs_idle_s"] / 4 + 1e-9


def test_readers_on_a_trace_without_scopes(traced_cell):
    """A program that writes no scopes and no spans (the parent of the PR
    that added them): the share reads 100, the rest is left out."""
    run, trace = traced_cell(UNSCOPED, 3)
    values = {m: read(m, run, trace) for m in NEW}
    assert values.pop("unscoped_device_share") == pytest.approx(100.0)
    assert set(values.values()) == {None}


@pytest.mark.parametrize("metric", NEW)
def test_reader_returns_none_on_a_cpu_rehearsal(metric):
    run = {"cell": "alexnet_f32", "traced_iters": 40, "chips": 1}
    assert read(metric, run, None) is None
    assert read(metric, {**run, "traced_iters": 0}, {"busy_s": 1.0}) is None
