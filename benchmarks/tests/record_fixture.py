#!/usr/bin/env python3
"""Record the trace reducer's test fixture on a four-chip host.

    chiprun --chips 4 -- python3 benchmarks/tests/record_fixture.py

A toy data-parallel step (matmul, a loop, the program's Pallas LRN on each
chip's shard, a gradient all-reduce) runs three times under the profiler,
inside the host span the `train` driver writes (and an outer one, so that
"innermost" is exercised), with a short sleep between steps so that there is
an idle gap the host can be blamed for. The
trace lands in `chiprun_out/bench/fixture/toy_dp4.xplane.pb`; the copy
checked in beside this file is what `test_bench_trace_reduce.py` reads.
"""

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmarks")]


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    import trace_reduce
    from caffe_mpi_tpu.ops.lrn import lrn_across_channels
    from caffe_mpi_tpu.parallel import MeshPlan

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print(f"needs 4 TPU chips; jax found {len(devices)} on "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    plan = MeshPlan.data_parallel(devices[:4])
    key = jax.random.PRNGKey(0)
    x = jax.device_put(jax.random.normal(key, (4096, 1024)),
                       plan.batch_sharded(2, 0))
    img = jax.device_put(
        jax.random.normal(key, (8, 96, 55, 55)).astype(jnp.bfloat16),
        plan.batch_sharded(4, 0))
    w = jax.device_put(jax.random.normal(key, (1024, 1024)) * 0.03,
                       plan.replicated())

    @jax.jit
    def step(w, x, img):
        def loss(w):
            h = jnp.tanh(x @ w)
            h = lax.fori_loop(0, 3, lambda i, h: jnp.tanh(h @ w), h)
            return jnp.mean(jnp.square(h))
        normed = plan.per_batch_shard(
            lambda t: lrn_across_channels(t, 5, 1e-4, 0.75, 1.0), img)
        return w - 0.1 * jax.grad(loss)(w), normed

    jax.block_until_ready(step(w, x, img))
    out = ROOT / "chiprun_out" / "bench" / "fixture"
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out / "trace"), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/traced_slice"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/solver.step"):
                w, normed = step(w, x, img)
                jax.block_until_ready((w, normed))
            with jax.profiler.TraceAnnotation("bench/between_blocks"):
                time.sleep(0.003)
    jax.profiler.stop_trace()
    xplane = next((out / "trace").glob("plugins/profile/*/*.xplane.pb"))
    shutil.copy(xplane, out / "toy_dp4.xplane.pb")
    print(trace_reduce.describe(str(xplane), first=3))
    return 0


if __name__ == "__main__":
    sys.exit(main())
