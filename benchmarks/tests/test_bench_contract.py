"""BENCHMARK.json against the files it names."""

import json
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# a metric's `layer` is a row's name in PERF.md's table of layers
PERF = (ROOT / "PERF.md").read_text()


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= bench["run_seconds"] <= 51


def test_names_are_plain_and_used_once(bench):
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(len(e["why"]) <= 200
               for key in ("configs", "workloads") for e in bench[key])


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for cell in bench["workloads"]:
        assert cell["chips"] in (1, 4)
        assert (ROOT / configs[cell["config"]]["file"]).is_file()
        mix = json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{mix['kind']}.py").is_file()
        pairs.add((cell["config"], cell["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    assert {c["config"] for c in bench["workloads"]} == set(configs)
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_metrics_and_their_readers(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert LAYER.match(m["layer"])
        assert f"| {m['layer']} " in PERF
        assert set(m.get("workloads", cells)) <= cells
    readers = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")}
    assert readers == {m["name"] for m in bench["per_layer"]}


def test_peaks_name_their_sources():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"]["value"] == 197e12
    assert v5e["hbm_bytes_per_s"]["value"] == 819e9
    assert all(entry["source"] for kind in peaks.values()
               for entry in kind.values())
