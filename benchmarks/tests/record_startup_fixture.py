#!/usr/bin/env python3
"""Record the start-up reducer's test fixture, on the CPU.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/record_startup_fixture.py

A toy net with an LRN layer, in bf16 so that the Pallas kernels are traced,
through `SolverParameter` -> `Solver` -> `solver.step`, as the `train` driver
runs a cell: the ledger's listeners installed first (where `run.py`
enables the compile cache), a `Solver` built, a second `Net` for a check,
the first step, a warm-up block, then a "window" of steps that builds
nothing. What lands in `benchmarks/tests/startup_ledger.json` is the record
a reader is handed (`setup_s`, `window_s`, `cell`) with the ledger's
snapshot under `startup_ledger`; `test_bench_startup_reduce.py` reads it.
The seconds are a CPU's: the fixture holds the reducer to its arithmetic,
not to a speed.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmarks")]

TOY = """
name: "toy"
layer { name: "in" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 8 dim: 3 dim: 8 dim: 8 } shape { dim: 8 } } }
layer { name: "conv" type: "Convolution" bottom: "data" top: "c"
        convolution_param { num_output: 8 kernel_size: 3
          weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "c" top: "c" }
layer { name: "norm" type: "LRN" bottom: "c" top: "n"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "ip" type: "InnerProduct" bottom: "n" top: "score"
        inner_product_param { num_output: 5
          weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "score" bottom: "label"
        top: "loss" }
"""


def main() -> int:
    import jax
    import numpy as np

    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver
    from caffe_mpi_tpu.utils import compile_cache, spans

    clock = time.perf_counter
    jax.devices()
    t0 = clock()
    compile_cache.install_ledger()
    sp = SolverParameter.from_text(
        'base_lr: 0.05 momentum: 0.9 lr_policy: "fixed" max_iter: 100 '
        'display: 0 random_seed: 3 precision: "bf16"')
    sp.net_param = NetParameter.from_text(TOY)
    solver = Solver(sp)
    try:
        Net(NetParameter.from_text(TOY), phase="TEST")
        rng = np.random.RandomState(0)
        feeds = {k: (rng.randint(0, 5, shape).astype(np.int32)
                     if len(shape) == 1
                     else rng.randn(*shape).astype(np.float32))
                 for k, (shape, _) in solver.net.feed_specs.items()}
        solver.step(1, lambda it: feeds)
        solver.step(4, lambda it: feeds)
        jax.block_until_ready(solver.params)
        t_begin = clock()
        solver.step(10, lambda it: feeds)
        jax.block_until_ready(solver.params)
        window_s = clock() - t_begin
    finally:
        solver.close()
    record = {"cell": "fixture", "setup_s": t_begin - t0,
              "window_s": window_s,
              "startup_ledger": spans.ledger.snapshot()}
    out = Path(__file__).with_name("startup_ledger.json")
    out.write_text(json.dumps(record, indent=0) + "\n")
    print(f"{out}: {out.stat().st_size} bytes, "
          f"{len(record['startup_ledger']['phases'])} phases, "
          f"{len(record['startup_ledger']['programs']['events'])} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
