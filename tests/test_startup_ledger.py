"""The start-up ledger (utils/spans.py `ledger`, utils/compile_cache.py
`programs`): phases nest, are bounded and cheap; jax's build events land in
one row a program with no second counted twice; a `Solver` build and its
first steps leave the phases the table names, in order, and none after the
first pass of the train loop; `layer_scope` and `trace/kernel` name what
cost Python seconds; `caffe train` logs the table once. Seconds here are
the CPU's: what a chip's host pays is in PERF.md."""

import ast
import inspect
import logging
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from caffe_mpi_tpu.solver import Solver
from caffe_mpi_tpu.tools.cli import main
from caffe_mpi_tpu.utils import compile_cache, spans

from test_spans import ROOT, TOY, toy_feeds, toy_solver


@pytest.fixture
def ledgers(monkeypatch):
    """A ledger of this test's own: the process's is one store for every
    test before it, and may stand at its cap."""
    monkeypatch.setattr(spans, "ledger", spans.Ledger())
    monkeypatch.setattr(compile_cache, "programs",
                        compile_cache.ProgramLedger())
    compile_cache.install_ledger()
    return spans.ledger, compile_cache.programs


# -- phases ------------------------------------------------------------------

def test_phases_nest_in_order_of_opening(ledgers):
    ledger, _ = ledgers
    with spans.phase("solver/build") as outer:
        with spans.phase("solver/opt state", slots=2) as inner:
            pass
        outer.stats["layers"] = 3
    with spans.phase("cli/feeders"):
        pass
    assert [(r.name, r.depth) for r in ledger.phases] == [
        ("solver/build", 0), ("solver/opt state", 1), ("cli/feeders", 0)]
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert inner.stats == {"slots": 2} and outer.stats == {"layers": 3}
    assert ledger.seconds("solver/build") == outer.seconds > 0
    # of two names where one lies inside the other, the outer counts once
    assert ledger.outermost("solver/build", "solver/opt state") == [outer]


def test_a_phase_that_raises_is_closed(ledgers):
    ledger, _ = ledgers
    with pytest.raises(ValueError):
        with spans.phase("net/build"):
            raise ValueError("no such layer")
    with spans.phase("net/build"):
        pass
    assert [r.depth for r in ledger.phases] == [0, 0]
    assert all(r.end is not None for r in ledger.phases)


def test_ten_thousand_phases_stay_under_the_cap_and_are_cheap(ledgers):
    ledger, _ = ledgers
    n, rounds = 10_000, []
    for _ in range(3):      # the best of three: the suite's other workers
        t0 = time.perf_counter()
        for _ in range(n):
            with spans.phase("parse", bytes=1):
                pass
        rounds.append(time.perf_counter() - t0)
    assert len(ledger.phases) == spans.PHASE_CAP < n
    assert ledger.dropped == 3 * n - spans.PHASE_CAP
    assert min(rounds) < 0.05, f"{min(rounds) * 1e6 / n:.2f} us a phase"
    assert "dropped" in ledger.table()


def test_a_phase_is_a_span_in_a_profiler_session(tmp_path, ledgers):
    from test_spans import profiled

    def body():
        with spans.phase("net/build", phase="TRAIN"):
            pass
    (events,) = profiled(body, tmp_path)
    assert [(e[0], e[3].get("phase")) for e in events] == [
        ("caffe/net/build", "TRAIN")]


def test_parse_phase_needs_no_jax():
    """The jax-free tools parse prototxts: the ledger records the phase
    there and opens no annotation."""
    code = textwrap.dedent("""
        import sys
        for m in ('jax', 'jaxlib'):
            sys.modules[m] = None
        from caffe_mpi_tpu.proto import NetParameter
        from caffe_mpi_tpu.utils import spans
        text = 'name: "n" layer { name: "a" type: "ReLU" }'
        NetParameter.from_text(text)
        (r,) = spans.ledger.phases
        assert (r.name, r.stats["message"]) == ("parse", "NetParameter"), r
        assert r.stats["bytes"] == len(text) == 42 and r.seconds > 0
        print(spans.ledger.table().splitlines()[1])
        """)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=60,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr
    assert "1 texts, 42 bytes" in out.stdout


def _loops(fn) -> list[ast.AST]:
    tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    return [n for n in ast.walk(tree) if isinstance(n, (ast.While, ast.For))]


def test_no_phase_is_opened_inside_the_loop_of_solver_step():
    """Phases are for work done once; `span` is the loop's only tool."""
    loops = _loops(Solver.step)
    assert "spans.iteration(" in ast.unparse(loops[0])   # the train loop
    assert not any("phase(" in ast.unparse(loop) for loop in loops)


# -- programs ----------------------------------------------------------------

def test_a_jitted_function_gets_one_row_and_a_second_call_adds_nothing(
        ledgers):
    _, programs = ledgers

    def startup_ledger_probe(x):
        return jnp.sum(jnp.where(x > 0, jnp.sin(x), 0.0))

    t0 = time.perf_counter()
    f = jax.jit(startup_ledger_probe)
    x = jnp.ones((16, 16))
    jax.block_until_ready(x)
    before = dict(programs.sums())
    jax.block_until_ready(f(x))
    wall = time.perf_counter() - t0
    row = programs.rows["startup_ledger_probe"]
    assert row.trace_s > 0 and row.lower_s > 0 and row.backend_s > 0
    assert row.built == 1 and len(row.built_at) == 1
    assert programs.installed_at <= t0 < row.built_at[0]
    # the `jnp` functions traced inside it have rows of their own, and
    # their seconds are inside the probe's: the events count each once
    assert programs.rows["_where"].trace_s > 0
    assert programs.rows["_where"].built == 0
    mine = [e for e in programs.events if e.name == "startup_ledger_probe"]
    assert [compile_cache.KINDS[e.kind] for e in mine] == [
        "trace", "lower", "backend"]
    assert not any(e.name == "_where" for e in programs.events)
    after = programs.sums()
    spent = sum(after[k] - before[k]
                for k in ("trace_s", "lower_s", "backend_s"))
    assert 0 < spent <= wall
    assert abs(spent - (row.trace_s + row.lower_s + row.backend_s)) < 2e-3
    snapshot = programs.snapshot()
    jax.block_until_ready(f(x))
    assert programs.snapshot() == snapshot


def test_events_inside_an_event_are_folded_into_it(ledgers, monkeypatch):
    """Fed by hand on a clock of the test's own: a trace of 1.0 s that
    holds a trace of 0.2 and an eager build of 0.3 (trace 0.05, lower
    0.05, backend 0.2, a cache hit) is one event of trace 0.75, lower
    0.05, backend 0.2; the rows keep what jax reported."""
    _, programs = ledgers
    now = [100.0]
    monkeypatch.setattr(compile_cache, "_clock", lambda: now[0])

    def at(t, event, seconds, name):
        now[0] = t
        compile_cache._on_duration(event, seconds, fun_name=name)

    at(100.30, compile_cache.TRACE_EVENT, 0.2, "inner")
    at(100.45, compile_cache.TRACE_EVENT, 0.05, "const")
    at(100.50, compile_cache.LOWER_EVENT, 0.05, "jit(const)")
    compile_cache._on_event(compile_cache.HIT_EVENT)
    compile_cache._on_duration(compile_cache.RETRIEVAL_EVENT, 0.15)
    at(100.70, compile_cache.BACKEND_EVENT, 0.2, "jit(const)")
    at(101.00, compile_cache.TRACE_EVENT, 1.0, "step")
    at(101.50, compile_cache.LOWER_EVENT, 0.4, "jit(step)")
    (traced, lowered) = programs.events
    assert (traced.name, lowered.name) == ("step", "step")
    assert traced.seconds == pytest.approx([0.75, 0.05, 0.2])
    assert (traced.built, traced.hits) == (1, 1)
    assert lowered.seconds == pytest.approx([0.0, 0.4, 0.0])
    assert programs.rows["step"].trace_s == pytest.approx(1.0)
    assert programs.rows["inner"].trace_s == pytest.approx(0.2)
    const = programs.rows["const"]
    assert (const.built, const.hits, const.misses) == (1, 1, 0)
    assert const.retrieval_s == pytest.approx(0.15)
    assert programs.sums(start=101.2)["lower_s"] == pytest.approx(0.4)
    assert programs.sums(end=101.2)["built"] == 1
    assert programs.built_between(100.0, 100.6) == 0


def test_the_event_store_is_bounded(ledgers, monkeypatch):
    _, programs = ledgers
    monkeypatch.setattr(compile_cache, "EVENT_CAP", 8)
    for i in range(20):
        compile_cache._on_duration(compile_cache.BACKEND_EVENT, 1e-9,
                                   fun_name="jit(tiny)")
        time.sleep(1e-4)    # apart, so that none lies inside the next
    assert len(programs.events) == 8 and programs.dropped == 12
    assert programs.rows["tiny"].built == 20
    assert len(programs.rows["tiny"].built_at) == 8


def test_listeners_from_many_threads_lose_no_update(ledgers):
    _, programs = ledgers
    workers, each = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                compile_cache._on_event(compile_cache.MISS_EVENT)
                compile_cache._on_duration(compile_cache.BACKEND_EVENT,
                                           1e-9, fun_name="jit(shared)")
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    row = programs.rows["shared"]
    assert row.built == row.misses == workers * each
    assert sum(e.built for e in programs.events) + programs.dropped \
        == row.built


def test_installing_twice_registers_one_listener_pair(ledgers):
    _, programs = ledgers
    stamp = programs.installed_at
    compile_cache.install_ledger()
    compile_cache.enable_compile_cache()
    assert programs.installed_at == stamp
    from jax._src import monitoring
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1


# -- a Solver's start-up -----------------------------------------------------

@pytest.fixture
def solver_file(tmp_path):
    (tmp_path / "net.prototxt").write_text(TOY)
    path = tmp_path / "solver.prototxt"
    path.write_text(f'net: "{tmp_path}/net.prototxt"\nbase_lr: 0.05\n'
                    f'lr_policy: "fixed" max_iter: 10 display: 3\n'
                    f'snapshot_prefix: "{tmp_path}/snap"\n')
    return str(path)


def test_a_solver_build_leaves_its_phases_in_order(ledgers, solver_file):
    from caffe_mpi_tpu.proto import SolverParameter
    ledger, programs = ledgers
    # the fillers' programs must be built here, not found in this
    # process's jit cache: another test file on the same xdist worker
    # (test_spans.py's toy net has the same shapes) may have built them
    jax.clear_caches()
    sp = SolverParameter.from_file(solver_file)
    solver = Solver(sp)
    solver.close()
    names = [r.name for r in ledger.phases]
    assert names == ["parse", "solver/build", "parse", "net/build",
                     "net/fill", "solver/opt state"]
    by = {r.name: r for r in ledger.phases}
    build = by["solver/build"]
    assert build.depth == 0 and by["parse"].depth == 1  # the net's text
    for inner in ("net/build", "net/fill", "solver/opt state"):
        assert by[inner].depth == 1
        assert build.start <= by[inner].start <= by[inner].end <= build.end
    assert by["net/build"].stats == {"phase": "TRAIN", "layers": 6}
    assert by["net/fill"].stats["parameters"] == sum(
        a.size for p in solver.params.values() for a in p.values()) == 1669
    assert ledger.phases[0].stats["message"] == "SolverParameter"
    # the fillers built programs, and the ledger says how many
    fill = by["net/fill"]
    assert programs.built_between(fill.start, fill.end) >= 2
    assert "solver/build" in ledger.table()


def test_three_steps_leave_one_step_row_and_no_phase_after_the_first_pass(
        ledgers):
    ledger, programs = ledgers
    solver = toy_solver()
    feeds = toy_feeds()
    second_pass = []

    def feed(it):
        if it == 1:
            second_pass.append(time.perf_counter())
        return feeds
    try:
        solver.step(3, feed)
        jax.block_until_ready(solver.params)
    finally:
        solver.close()
    row = programs.rows["step"]
    assert row.built == 1
    assert row.trace_s > 0 and row.lower_s > 0 and row.backend_s > 0
    assert "solver/jit" in [r.name for r in ledger.phases]
    (stamp,) = second_pass
    assert all(r.end < stamp for r in ledger.phases)
    assert all(e.end < stamp for e in programs.events if e.name == "step")
    assert "program `step`" in ledger.table()


def test_layer_scope_fills_apply_seconds_for_every_layer_type(ledgers):
    ledger, programs = ledgers
    solver = toy_solver()
    try:
        solver.step(1, lambda it: toy_feeds())
    finally:
        solver.close()
    types = {layer.lp.type for layer in solver.net.layers}
    assert types == {"Input", "Convolution", "ReLU", "LRN", "InnerProduct",
                     "SoftmaxWithLoss"}
    assert set(ledger.apply_s) == types
    assert all(s > 0 for s in ledger.apply_s.values())
    # Python inside the layers is a part of the step's trace, not all of it
    assert sum(ledger.apply_s.values()) < programs.rows["step"].trace_s


def test_nested_layer_scopes_count_a_second_once(ledgers):
    ledger, _ = ledgers

    class Fake:
        def __init__(self, type_, name):
            self.lp = type("LP", (), {"type": type_})()
            self.name = name
    t0 = time.perf_counter()
    with spans.layer_scope(Fake("Pipeline", "pp")):
        with spans.layer_scope(Fake("InnerProduct", "pp/ip")):
            time.sleep(0.02)
    wall = time.perf_counter() - t0
    assert ledger.apply_s["InnerProduct"] >= 0.02
    assert sum(ledger.apply_s.values()) <= wall
    assert ledger.apply_s["Pipeline"] < 0.01


def test_trace_kernel_names_the_kernel_and_its_branch(ledgers):
    """bf16 sends LRN through the Pallas kernels; wherever the platform is
    not known at trace time jax traces both arms, and each is a phase."""
    ledger, _ = ledgers
    solver = toy_solver('precision: "bf16"')
    try:
        solver.step(1, lambda it: toy_feeds())
    finally:
        solver.close()
    kernels = ledger.kernels()
    assert set(kernels) == {"lrn_fwd", "lrn_bwd"}
    for row in kernels.values():
        assert set(row["branches"]) == {"cpu", "default"}
        assert row["n"] == sum(row["branches"].values()) and row["s"] > 0
    assert all(r.depth >= 0 and r.stats["kernel"].startswith("lrn_")
               for r in ledger.outermost(spans.KERNEL))
    assert "lrn_" in ledger.table().splitlines()[-1]


def test_a_kernel_called_twice_is_traced_once_an_arm(ledgers):
    """`pallas_call` builds its two arms once: jax's trace caches key on
    their identity, and arms rebuilt a call made every call of a memoized
    builder's kernel a trace of its own (10 s of warm set-up in the
    language-model cells, PERF.md section 6, PR 35)."""
    from caffe_mpi_tpu.ops.pallas_call import pallas_call
    ledger, _ = ledgers

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0
    x = jnp.ones((8, 128), jnp.float32)
    call = pallas_call(double, name="double",
                       out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))
    out = jax.jit(lambda x: call(call(call(x))))(x)
    assert float(out[0, 0]) == 8.0
    assert ledger.kernels() == {"double": {
        "s": pytest.approx(ledger.seconds(spans.KERNEL)), "n": 2,
        "branches": {"cpu": 1, "default": 1}}}


def test_snapshot_is_plain_json(ledgers):
    import json
    ledger, _ = ledgers
    solver = toy_solver()
    try:
        solver.step(1, lambda it: toy_feeds())
    finally:
        solver.close()
    snap = json.loads(json.dumps(ledger.snapshot()))
    assert {"phases", "phases_dropped", "apply_s", "programs"} == set(snap)
    assert {"installed_at", "dropped", "rows", "events"} == set(
        snap["programs"])
    assert snap["programs"]["rows"]["step"]["built"] == 1
    name, kind, start, end, *seconds, built, hits, misses = next(
        e for e in snap["programs"]["events"] if e[0] == "step")
    assert kind == "trace" and end - start == pytest.approx(sum(seconds))


# -- the operator's table ----------------------------------------------------

def test_train_logs_the_table_once(ledgers, solver_file, caplog):
    with caplog.at_level(logging.INFO, logger="caffe"):
        assert main(["train", "-solver", solver_file, "-synthetic"]) == 0
    tables = [r.getMessage() for r in caplog.records
              if "Where start-up time went" in r.getMessage()]
    assert len(tables) == 1
    labels = [line.split("  ")[1].strip() if line.startswith("  ") else line
              for line in tables[0].splitlines()]
    assert labels[1:] == ["parse", "net/build", "net/fill", "solver/build",
                          "cli/feeders", "cli/first step", "program `step`",
                          "programs", "layer Python", "kernel traces"]
    ledger, programs = ledgers
    first = ledger.outermost("cli/first step")
    assert len(first) == 1 and first[0].depth == 0
    assert first[0].start <= programs.rows["step"].built_at[0] \
        <= first[0].end
