"""The command itself, off the chip: refusal without a TPU, the rehearsal's
control flow on the CPU, and discovery of dropped-in files by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

PROGRAM = ("caffe_mpi_tpu", "models")


def run_cell(root, *args, env=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("CAFFE_", "XLA_FLAGS"))} | (env or {})
    proc = subprocess.run(
        [sys.executable, str(root / "benchmarks" / "run.py"), *args],
        cwd=root, env=env, text=True, capture_output=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    return proc, lines


def test_off_the_chip_it_refuses_and_names_the_platform():
    proc, lines = run_cell(ROOT, "--workload", "alexnet_f32", "--seed", "0",
                           "--seconds", "1", "--trace", "0",
                           env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert lines == []
    assert "platform 'cpu'" in proc.stderr and "No result" in proc.stderr


def test_a_set_program_switch_is_refused():
    proc, lines = run_cell(ROOT, "--workload", "alexnet_f32", "--rehearse",
                           env={"CAFFE_LRN_PALLAS": "0"})
    assert proc.returncode != 0 and lines == []
    assert "CAFFE_LRN_PALLAS" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc, lines = run_cell(tmp_path, "--workload", "alexnet_f32",
                           "--rehearse")
    assert proc.returncode != 0 and lines == []


def test_dp4_rehearses_on_four_virtual_devices():
    proc, lines = run_cell(ROOT, "--workload", "alexnet_f32_dp4", "--seed",
                           "3", "--seconds", "0.1", "--trace", "0",
                           "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    window = next(line for line in lines if "window_s" in line)
    assert window["samples_per_iter"] == 8      # 2 a chip on 4 chips
    assert window["compiles_in_window"] == 0
    assert window["dispatches"] == window["iters"] == last["attempted"]


@pytest.fixture(scope="module")
def copy_with_dropped_in_files(tmp_path_factory):
    """A checkout's worth of files with one more traffic mix, one more
    per-layer metric and a cell that names them: files and entries added,
    nothing that was there edited."""
    root = tmp_path_factory.mktemp("checkout")
    for name in PROGRAM:
        os.symlink(ROOT / name, root / name)
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((BENCH / "traffic" / "train_f32_b1024.json").read_text())
    mix["block_iters"] = 10
    (root / "benchmarks" / "traffic" / "train_f32_new.json").write_text(
        json.dumps(mix))
    (root / "benchmarks" / "layer_metrics" / "blocks_in_window.py").write_text(
        '"""A metric dropped in by a later PR."""\n\n\n'
        'def compute(run, trace):\n'
        '    return run["iters"] / run["block_iters"]\n')
    bench["workloads"].append({
        "name": "alexnet_new", "config": "alexnet",
        "traffic": "train_f32_new", "chips": 1, "why": "dropped in"})
    bench["per_layer"].append({
        "name": "blocks_in_window", "unit": "blocks", "better": "higher",
        "source": "program_counter", "layer": "Solver_loop",
        "moves": "train_samples_per_s", "workloads": ["alexnet_new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    proc, lines = run_cell(root, "--workload", "alexnet_new", "--seed", "4",
                           "--seconds", "0.1", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    return root, lines


def test_dropped_in_mix_and_metric_are_found_by_name(
        copy_with_dropped_in_files):
    root, lines = copy_with_dropped_in_files
    window = next(line for line in lines if "window_s" in line)
    assert window["block_iters"] == 10           # the new mix was read
    readers = next(line for line in lines if "rehearsal" in line)[
        "layer_metric_readers_with_a_value"]
    assert "blocks_in_window" in readers          # and the new reader ran
    assert "allreduce_ms_per_step" not in readers  # another cell's metric
    # no file that was there differs
    for path in BENCH.rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            twin = root / "benchmarks" / path.relative_to(BENCH)
            assert twin.read_bytes() == path.read_bytes(), path


def test_one_chip_rehearsal_runs_traced_and_prints_no_device_metric(
        copy_with_dropped_in_files):
    root, lines = copy_with_dropped_in_files
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["metrics"] == {} and last["correct"] is True
    # one block before the traced slice, two in it, ten iterations each
    assert last["failed"] == 0 and last["attempted"] == 30
    logits = next(line for line in lines if line.get("check") == "logits")
    assert logits["ok"] and logits["rel_rms"] < 1e-4
    out = root / "chiprun_out" / "bench" / "alexnet_new"
    assert (out / "run.jsonl").is_file()
    assert list((out / "trace").glob("plugins/profile/*/*.xplane.pb"))
