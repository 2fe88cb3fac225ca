#!/usr/bin/env python3
"""Record `span_reduce.py`'s test fixture on one chip.

    chiprun -- python3 benchmarks/tests/record_scoped_fixture.py

A toy prototxt (convolution, ReLU, LRN, pooling, a layer whose name holds a
`/`, inner product, loss) trains in bf16 through the program's own `Solver`,
so that the trace carries what a cell's does: layer scopes forward and
backward, `solver.update`, the Pallas LRN kernels under their own names, and
the `caffe/solver/*` host spans. The batch comes from host memory each
iteration, so `feed wait` holds a transfer and the device idles between
programs while the host is inside a named span. Four iterations run under
the profiler after two outside it; iteration 3 is a display boundary.

The trace lands in `chiprun_out/bench/fixture/toy_scoped.xplane.pb`; the
copy checked in beside this file is what `test_bench_span_reduce.py` reads.
"""

import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmarks")]

NET = """
name: "toy_scoped"
layer { name: "data" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 64 dim: 3 dim: 32 dim: 32 }
                      shape { dim: 64 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 32 kernel_size: 5 stride: 1
                            weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "block/fc" type: "InnerProduct" bottom: "pool1" top: "fc1"
        inner_product_param { num_output: 128
                              weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "relu2" type: "ReLU" bottom: "fc1" top: "fc1" }
layer { name: "fc2" type: "InnerProduct" bottom: "fc1" top: "fc2"
        inner_product_param { num_output: 10
                              weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc2" bottom: "label"
        top: "loss" }
"""


def record(out: Path) -> Path:
    import jax
    import numpy as np

    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver

    sp = SolverParameter.from_text(
        'base_lr: 0.01 momentum: 0.9 lr_policy: "fixed" max_iter: 100 '
        'display: 3 random_seed: 1 precision: "bf16"')
    sp.net_param = NetParameter.from_text(NET)
    sp.snapshot_prefix = str(out / "snapshot")
    solver = Solver(sp)
    rng = np.random.RandomState(0)
    feeds = {"data": rng.randn(64, 3, 32, 32).astype(np.float32),
             "label": rng.randint(0, 10, 64).astype(np.int32)}
    shutil.rmtree(out / "trace", ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        solver.step(2, lambda it: feeds)
        jax.block_until_ready(solver.params)
        jax.profiler.start_trace(str(out / "trace"), profiler_options=options)
        solver.step(4, lambda it: feeds)
        jax.block_until_ready(solver.params)
        jax.profiler.stop_trace()
    finally:
        solver.close()
    xplane = next((out / "trace").glob("plugins/profile/*/*.xplane.pb"))
    shutil.copy(xplane, out / "toy_scoped.xplane.pb")
    return xplane


def main() -> int:
    import jax

    import span_reduce
    import trace_reduce

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU chip; jax found {len(devices)} device(s) on "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    out = ROOT / "chiprun_out" / "bench" / "fixture"
    out.mkdir(parents=True, exist_ok=True)
    xplane = record(out)
    print(trace_reduce.describe(str(xplane), first=3))
    return span_reduce.main([str(xplane)])


if __name__ == "__main__":
    sys.exit(main())
