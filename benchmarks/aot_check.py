#!/usr/bin/env python3
"""Compile a cell's train step for a described v5e host, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check.py <cell>[@<batch per chip>] ...

The installed libtpu compiles for `v5e:2x2` with nothing attached. This
builds the cell's `Solver` on the CPU exactly as the `train` driver does, at
the real batch, hands the solver's own iteration function abstract arguments
placed on the described chips, and prints what the compiler says: the bytes
`memory_analysis()` counts on one chip, and how many Mosaic calls and
all-reduces the step holds. What the compiler refuses here costs no chip
time. Nothing runs, so nothing here is a time or a rate; the memory is one
program's, not what else the process keeps on the device.

It reaches into `Solver` (`_iteration_fn`, `_guard_state0`) the way
`tests/test_tpu_aot_compile.py::train_step_text` does: there is no public
way to lower the step without running it.
"""

import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]


def compile_step(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding

    import run as harness
    from caffe_mpi_tpu.parallel import MeshPlan
    train = harness.load_module(BENCH / "drivers" / "train.py")

    chips = cell["chips"]
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    job = train.build_job(cell, 0, BENCH.parent / "chiprun_out" / "aot")
    solver = job.solver
    try:
        if cell["traffic"]["mesh"] == "data_parallel":
            plan = MeshPlan(mesh=Mesh(
                np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model")))
            solver.net.bind_mesh(plan)
            rep = plan.replicated()
            feed_sharding = lambda ndim: plan.batch_sharded(ndim, 1)
        else:
            rep = SingleDeviceSharding(topo.devices[0])
            feed_sharding = lambda ndim: rep
        abstract = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                           sharding=rep), tree)
        feeds = {
            k: jax.ShapeDtypeStruct(
                (1, *shape), jnp.int32 if len(shape) == 1 else jnp.float32,
                sharding=feed_sharding(len(shape) + 1))
            for k, (shape, _) in solver.net.feed_specs.items()}
        args = [abstract(solver.params), abstract(solver.net_state),
                abstract(solver.opt_state), feeds, abstract(jnp.int32(0)),
                abstract(solver.base_rng)]
        if solver._guard_on:  # bf16's loss scale rides the guard carry
            args.append(abstract(solver._guard_state0()))
        return (jax.jit(solver._iteration_fn(), donate_argnums=(0, 1, 2))
                .trace(*args).lower(lowering_platforms=("tpu",)).compile())
    finally:
        solver.close()


def main(names: list[str]) -> int:
    import run as harness
    for name in names:
        name, _, per_chip = name.partition("@")
        cell = harness.load_cell(name, rehearse=False)
        if per_chip:  # size a cell before its traffic file is written
            cell["traffic"]["batch_per_chip"] = int(per_chip)
        t = time.perf_counter()
        compiled = compile_step(cell)
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(json.dumps({
            "cell": name,
            "batch_per_chip": cell["traffic"]["batch_per_chip"],
            "compile_s": round(time.perf_counter() - t, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "live_bytes_one_chip": live,
            "tpu_custom_calls": len(re.findall(
                r'custom_call_target="tpu_custom_call"', text)),
            "all_reduces": len(re.findall(
                r" all-reduce(?:-start)?\(", text)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
