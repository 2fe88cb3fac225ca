"""The benchmark's compressed-convolutional-attention cell
(zaya1_bf16_s8k_ep2share) at its published widths, compiled for a described
v5e with no chip present (tests/test_tpu_aot_compile.py has the helpers and
the other cells): the flash kernels at its head counts, and the whole train
step as the `train_cca_lm` driver builds it, whose bytes chose the depth."""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from test_tpu_aot_compile import (abstract, bench_harness, compile_tpu,
                                  kernel_calls, on_chip, step_calls,
                                  v5e_devices)

ROOT = Path(__file__).resolve().parent.parent


def test_flash_at_the_cell_s_grouped_heads():
    """8 query heads over 2 key/value heads of 128, causal, 8,192 rows,
    head-major as the layer hands them over."""
    from caffe_mpi_tpu.ops.flash_attention import flash_attention_heads
    q = on_chip((1, 8, 8192, 128), jnp.bfloat16)
    kv = on_chip((1, 2, 8192, 128), jnp.bfloat16)

    def f(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention_heads(
            q, k, v, causal=True), q, k, v)
        return out, vjp(out)
    names = sorted(re.sub(r"\.\d+$", "", name)
                   for name, _ in kernel_calls(compile_tpu(f, q, kv, kv)))
    assert names == ["flash_dkv", "flash_dq", "flash_fwd"]


def test_whole_step_compiles_and_fits_the_chip():
    bench = ROOT / "benchmarks"
    sys.path[:0] = [p for p in (str(bench),) if p not in sys.path]
    harness = bench_harness()
    driver = harness.load_module(bench / "drivers" / "train_cca_lm.py")
    cell = harness.load_cell("zaya1_bf16_s8k_ep2share", rehearse=False)
    blocks = cell["config"]["num_hidden_layers"]
    job = driver.train.build_job(
        cell, 0, Path(os.environ.get("TMPDIR", "/tmp")) / "aot_cca_lm")
    solver = job.solver
    try:
        assert not solver._guard_on   # static loss scale: one state
        rep = SingleDeviceSharding(v5e_devices()[0])
        feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
                 for k, (shape, _) in solver.net.feed_specs.items()}
        assert {k: v.shape for k, v in feeds.items()} == {
            "tokens": (1, 8192), "label": (1, 8192)}
        # the head has no blob of its own: the table's, under `embed`
        assert "logits" not in solver.params
        args = [abstract(solver.params, rep),
                abstract(solver.net_state, rep),
                abstract(solver.opt_state, rep), feeds,
                abstract(jnp.int32(0), rep),
                abstract(solver.base_rng, rep)]
        compiled = (jax.jit(solver._iteration_fn(plain=True),
                            donate_argnums=(0, 1, 2))
                    .trace(*args).lower(lowering_platforms=("tpu",))
                    .compile())
    finally:
        solver.close()
    mem = compiled.memory_analysis()
    # f32 masters and Adam's two slots, 12 bytes a parameter
    assert mem.argument_size_in_bytes >= 12 * 708_664_684
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # six blocks are the deepest under the configuration's limit (PERF.md
    # section 4, PR 38: 11.36e9 at five, 13.36e9 at six, 15.27e9 at seven,
    # with the attention layers' `remat: true`)
    assert blocks == 6 and live < 14.5e9, live
    calls, fallback = step_calls(compiled.as_text())
    flash = [c for c in calls if c.startswith("flash_")]
    # remat does not run the forward kernel a second time
    assert sorted(flash) == (["flash_dkv"] * blocks + ["flash_dq"] * blocks
                             + ["flash_fwd"] * blocks)
    expected = cell["config"]["checks"]["pallas_calls_per_step"]["bf16"]
    assert len(calls) == expected == 12 * blocks, (len(calls), expected)
    assert fallback.count("ragged-dot-none") == blocks * 11, fallback
