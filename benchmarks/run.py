#!/usr/bin/env python3
"""The benchmark's one command: run one cell once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, when traced, `breakdown`.
With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. Earlier lines (JSON too, one object
each) carry what else is worth reading; they are also written to
`chiprun_out/bench/<cell>/run.jsonl`.

Everything that belongs to one cell is data, found by the names in
`BENCHMARK.json`: the configuration's file, `traffic/<mix>.json`, the
driver `drivers/<kind>.py` named by the mix's `kind`, and one reader
`layer_metrics/<metric>.py` for each per-layer metric. Adding a cell, a
mix, a driver or a metric adds files and an entry, and edits nothing here.

The process imports jax itself (one process per chip) and refuses any
platform but `tpu`, or fewer chips than the cell asks for: exit code 2 and
no result. `--rehearse` runs the same control flow on the CPU at the tiny
preset of the configuration's file and prints `"metrics": {}`: a number
from a CPU never sits under a device metric's name.
"""

import time

T0 = time.perf_counter()  # process start, for the earlier lines only

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
EXIT_NO_DEVICE = 2
EXIT_NO_PROGRAM = 3


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, rehearse: bool) -> dict:
    """The workload entry with its configuration and traffic files read
    in, and the metric entries that apply to it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    cell = dict(entries[name])
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    cell["config"] = json.loads((ROOT / config_entry["file"]).read_text())
    cell["traffic"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["preset"] = cell["config"]["rehearse"] if rehearse else {}
    applies = lambda m: name in m.get("workloads", [name])
    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    cell["run_seconds"] = bench["run_seconds"]
    return cell


def layer_metrics(cell: dict, record: dict, trace: dict | None) -> dict:
    """Each per-layer metric from its own reader. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry in cell["per_layer"]:
        reader = load_module(BENCH / "layer_metrics" / f"{entry['name']}.py")
        value = reader.compute(record, trace)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, args.rehearse)
    chips = cell["chips"]
    switches = sorted(k for k in os.environ if k.startswith("CAFFE_"))
    if switches:
        raise SystemExit(f"the benchmark measures the program as committed; "
                         f"unset {switches}")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()
    sys.path.insert(0, str(ROOT))
    if importlib.util.find_spec("caffe_mpi_tpu") is None:
        print(f"benchmark: the program (caffe_mpi_tpu) is not in {ROOT}. "
              f"No result.", file=sys.stderr)
        return EXIT_NO_PROGRAM

    import jax
    t_imported = time.perf_counter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: jax found no device: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    # Set-up is counted from here, once the device runtime is up. What lies
    # before (importing jax, 2.8 s, and the TPU runtime's own start, 6.5 s to
    # over 20 s from run to run on the same machine) is neither the
    # program's nor the benchmark's, and would drown the rest in noise; it is
    # printed on the first line.
    t_ready = time.perf_counter()
    platform = devices[0].platform
    wanted = "cpu" if args.rehearse else "tpu"
    if platform != wanted or len(devices) < chips:
        print(f"benchmark: {cell['name']} needs {chips} {wanted} device(s); "
              f"jax found {len(devices)} on platform {platform!r} "
              f"({devices[0].device_kind}). No result.", file=sys.stderr)
        return EXIT_NO_DEVICE

    # the program's own cache rule (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache), and every program kept however quickly it
    # compiled, so that a warm run compiles nothing. A rehearsal keeps no
    # cache: nothing reads a CPU program twice.
    cache_dir = None
    if not args.rehearse:
        from caffe_mpi_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        peaks = json.loads((BENCH / "peaks.json").read_text())
        kind = devices[0].device_kind
        if kind not in peaks:
            raise SystemExit(f"no peaks on record for device kind {kind!r}; "
                             f"add it to benchmarks/peaks.json with its "
                             f"source")

    out_dir = ROOT / "chiprun_out" / "bench" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    seconds = cell["run_seconds"] if args.seconds is None else args.seconds

    with open(out_dir / "run.jsonl", "a") as log:
        def say(**fields):
            line = json.dumps(fields)
            print(line, flush=True)
            log.write(line + "\n")

        say(cell=cell["name"], seed=args.seed, seconds=seconds,
            trace=args.trace, rehearse=args.rehearse, platform=platform,
            device_kind=devices[0].device_kind, devices=len(devices),
            compile_cache=cache_dir, import_jax_s=t_imported - T0,
            runtime_start_s=t_ready - t_imported)
        driver = load_module(
            BENCH / "drivers" / f"{cell['traffic']['kind']}.py")
        result = driver.run(cell, seed=args.seed, seconds=seconds,
                            trace=bool(args.trace), t0=t_ready, out_dir=out_dir,
                            say=say)
        record, trace = result["record"], result["trace"]
        if not args.rehearse:
            record["peaks"] = {k: v["value"] for k, v in peaks[kind].items()}

        device = {"platform": platform, "kind": devices[0].device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": record["memory_peak_bytes"]}
        final = {"correct": result["correct"],
                 "attempted": result["attempted"],
                 "failed": result["failed"], "metrics": {}, "device": device}
        if args.rehearse:
            readers = layer_metrics(cell, record, trace) if args.trace \
                else {}
            say(rehearsal="no device metric is printed from a CPU",
                layer_metric_readers_with_a_value=sorted(readers))
        elif args.trace:
            if trace is None:
                raise SystemExit("traced run on a TPU, but the trace holds "
                                 "no TPU device plane")
            say(traced_run_end_to_end=result["end_to_end"],
                note="tracing overhead check; not held to any bound")
            final["metrics"] = layer_metrics(cell, record, trace)
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            final["breakdown"] = {"device_ops": trace["device_ops"],
                                  "idle_gaps": trace["idle_gaps"]}
        else:
            units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
            final["metrics"] = {
                name: {"value": value, "unit": units[name]}
                for name, value in result["end_to_end"].items()
                if name in units}
        line = json.dumps(final)
        log.write(line + "\n")
    print(line, flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
