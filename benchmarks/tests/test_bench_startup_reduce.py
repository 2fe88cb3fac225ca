"""startup_reduce.py and the ten `setup_*` readers: on a recorded ledger
(`startup_ledger.json`, recorded by `record_startup_fixture.py`), on a
ledger written by hand where every number is known, on a program that has
no ledger, and in a traced rehearsal of a cell."""

import json

import pytest

import startup_reduce
from conftest import BENCH, ROOT
from run import load_module
from test_bench_command import run_cell

NEW = ["setup_net_build_s", "setup_fill_s", "setup_trace_s", "setup_lower_s",
       "setup_backend_s", "setup_programs_built", "setup_step_program_s",
       "setup_layer_apply_s", "setup_kernel_trace_s", "setup_unaccounted_s"]


@pytest.fixture(scope="module")
def recorded() -> dict:
    return json.loads((BENCH / "tests" / "startup_ledger.json").read_text())


def read(name: str, run: dict):
    return load_module(BENCH / "layer_metrics" / f"{name}.py").compute(
        run, None)


def test_the_ten_are_the_metrics_that_move_setup_s_beside_the_old_two():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    moving = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in moving] == ["setup_build_s",
                                           "setup_compile_s"] + NEW
    assert [m["name"] for m in bench["per_layer"][-10:]] == NEW
    for m in moving[2:]:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["unit"] == ("programs" if m["name"].endswith("built")
                             else "s")
        assert m["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("name", NEW)
def test_every_reader_returns_a_number_on_the_recorded_ledger(name, recorded):
    value = read(name, recorded)
    assert isinstance(value, (int, float)) and value == value
    assert value >= 0, "the toy run's set-up holds every part"
    if name != "setup_programs_built":
        assert value < recorded["setup_s"]


def test_the_recorded_account_adds_up(recorded):
    reduced = startup_reduce.of_run(recorded)
    account, metrics = reduced["account"], reduced["metrics"]
    assert account["top_level_phases_s"] \
        + account["programs_outside_phases_s"] \
        + metrics["setup_unaccounted_s"] == pytest.approx(
            recorded["setup_s"], abs=1e-9)
    # the step's program was built outside any phase, after `solver/jit`
    assert metrics["setup_step_program_s"] \
        <= account["programs_outside_phases_s"]
    assert 0 < metrics["setup_layer_apply_s"] < metrics["setup_trace_s"]
    assert 0 < metrics["setup_kernel_trace_s"] \
        < metrics["setup_layer_apply_s"]
    assert set(reduced["kernel_traces"]) == {
        "lrn_fwd/cpu", "lrn_fwd/default", "lrn_bwd/cpu", "lrn_bwd/default"}
    assert reduced["programs_largest"]["step"]["built"] == 1
    assert metrics["setup_programs_built"] == reduced["programs"]["built"] > 5
    # the window built nothing, as the driver's own listener must find
    assert reduced["built_in_window"] == 0
    assert reduced["phases"]["net/build"]["n"] == 2
    assert reduced["phases"]["solver/build"]["top_level_s"] \
        == reduced["phases"]["solver/build"]["s"]
    assert reduced["phases"]["net/fill"]["top_level_s"] == 0


def by_hand() -> dict:
    """Installed at 10.0, set-up 10 s. `solver/build` [10.5, 14.5] holds
    `parse`, `net/build`, `net/fill` and `solver/opt state`; a second
    `parse` + `net/build` for a check stand alone [15, 15.5]; the step's
    three events [16, 18.5] lie outside any phase, with two kernel traces
    inside the trace; a filler's program is built inside `net/fill`; one
    program is built in the window and one phase opens after it."""
    phases = [
        ["parse", 10.5, 10.6, 1, {"message": "NetParameter", "bytes": 900}],
        ["solver/build", 10.5, 14.5, 0, {}],
        ["net/build", 10.6, 11.0, 1, {"phase": "TRAIN", "layers": 4}],
        ["net/fill", 11.0, 13.0, 1, {"layers": 4, "parameters": 100}],
        ["solver/opt state", 13.0, 13.5, 1, {}],
        ["parse", 15.0, 15.1, 0, {"message": "NetParameter", "bytes": 900}],
        ["net/build", 15.1, 15.5, 0, {"phase": "TEST", "layers": 4}],
        ["trace/kernel", 16.2, 16.4, 0, {"kernel": "k", "branch": "cpu"}],
        ["trace/kernel", 16.4, 16.5, 0, {"kernel": "k", "branch": "default"}],
        ["parse", 30.0, 31.0, 0, {"message": "NetParameter", "bytes": 1}],
    ]
    events = [      # name, kind, start, end, trace, lower, backend, b, h, m
        ["_normal", "backend", 11.5, 12.0, 0.1, 0.1, 0.3, 1, 1, 0],
        ["step", "trace", 16.0, 17.0, 1.0, 0.0, 0.0, 0, 0, 0],
        ["step", "lower", 17.0, 17.5, 0.1, 0.4, 0.0, 0, 0, 0],
        ["step", "backend", 17.5, 18.5, 0.0, 0.0, 1.0, 1, 0, 1],
        ["late", "backend", 21.0, 21.5, 0.0, 0.0, 0.5, 1, 0, 1],
    ]
    return {"cell": "by_hand", "setup_s": 10.0, "window_s": 5.0,
            "startup_ledger": {
                "phases": phases, "phases_dropped": 0,
                "apply_s": {"Convolution": 0.25, "LRN": 0.5},
                "programs": {"installed_at": 10.0, "dropped": 0, "rows": {},
                             "events": events}}}


@pytest.mark.parametrize("name,expected", [
    ("setup_net_build_s", 0.1 + 0.4 + 0.1 + 0.4),
    ("setup_fill_s", 2.0 + 0.5),
    ("setup_trace_s", 0.1 + 1.0 + 0.1),
    ("setup_lower_s", 0.1 + 0.4),
    ("setup_backend_s", 0.3 + 1.0),
    ("setup_programs_built", 2),
    ("setup_step_program_s", 2.5),
    ("setup_layer_apply_s", 0.75),
    ("setup_kernel_trace_s", 0.3),
    ("setup_unaccounted_s", 10.0 - (4.0 + 0.1 + 0.4) - 2.5),
])
def test_a_ledger_written_by_hand_reduces_to_what_it_holds(name, expected):
    assert read(name, by_hand()) == pytest.approx(expected)


def test_what_was_built_in_the_window_is_counted_apart():
    reduced = startup_reduce.of_run(by_hand())
    assert reduced["built_in_window"] == 1
    assert reduced["programs"] == {
        "trace_s": pytest.approx(1.2), "lower_s": pytest.approx(0.5),
        "backend_s": pytest.approx(1.3), "built": 2, "hits": 1, "misses": 1}
    assert reduced["account"]["programs_outside_phases"] == {
        "trace_s": pytest.approx(1.1), "lower_s": pytest.approx(0.4),
        "backend_s": pytest.approx(1.0)}
    assert reduced["phases"]["parse"] == {
        "s": pytest.approx(0.2), "top_level_s": pytest.approx(0.1), "n": 2}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_a_ledger_leaves_the_metric_out(name, monkeypatch):
    """The parent of PR 35 has `utils/spans.py` and no `ledger` in it: the
    reader returns None, does not raise, and prints nothing."""
    from caffe_mpi_tpu.utils import spans
    monkeypatch.delattr(spans, "ledger")
    assert read(name, {"cell": "parent", "setup_s": 7.0}) is None


def test_a_ledger_that_never_listened_leaves_the_metrics_out():
    run = by_hand()
    run["startup_ledger"]["programs"]["installed_at"] = None
    assert startup_reduce.of_run(run) is None


def test_a_traced_rehearsal_reads_all_ten_and_prints_the_earlier_line():
    proc, lines = run_cell(ROOT, "--workload", "alexnet_bf16", "--seed",
                           "2147483777", "--seconds", "0.1", "--trace", "1",
                           "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    readers = next(line for line in lines
                   if "layer_metric_readers_with_a_value" in line)
    assert set(NEW) <= set(readers["layer_metric_readers_with_a_value"])
    (earlier,) = [line["startup_ledger"] for line in lines
                  if "startup_ledger" in line]
    account = earlier["account"]
    assert account["top_level_phases_s"] \
        + account["programs_outside_phases_s"] + account["unaccounted_s"] \
        == pytest.approx(account["setup_s"], abs=1e-9)
    assert earlier["programs_largest"]["step"]["built"] == 1
    assert {"lrn_fwd/cpu", "lrn_fwd/default"} <= set(earlier["kernel_traces"])
    assert earlier["apply_s_by_layer_type"]["LRN"] > 0
    assert earlier["built_in_window"] == 0
    assert lines[-1]["metrics"] == {} and lines[-1]["correct"] is True
    assert (ROOT / "chiprun_out" / "bench" / "alexnet_bf16"
            / "startup.json").is_file()
