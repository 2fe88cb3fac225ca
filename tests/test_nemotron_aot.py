"""The benchmark's state-space cell (nemotron3_nano_bf16_s8k_ep16share) at
its published widths, compiled for a described v5e with no chip present
(tests/test_tpu_aot_compile.py has the helpers and the other cells): the
chunked scan with its backward pass, the flash kernels at its head counts,
the ungated grouped products, and the whole train step as the
`train_ssm_lm` driver builds it, whose bytes chose the depth."""

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


def test_the_chunked_scan_and_its_backward_pass_are_plain_xla():
    """64 heads of 64 lanes, a state of 128 in 8 groups, 64 chunks of 128:
    no Mosaic call, and the Q x Q matrices are not kept for the backward
    pass (the carried states are: 134 MB; the matrices would be 268 MB in
    float32 a layer)."""
    from caffe_mpi_tpu.ops.ssd import ssd
    x = on_chip((1, 8192, 64, 64), jnp.bfloat16)
    dt = on_chip((1, 8192, 64), jnp.bfloat16)
    bc = on_chip((1, 8192, 8, 128), jnp.bfloat16)
    head = on_chip((64,), jnp.float32)

    def f(x, dt, a_log, b, c, d, dt_bias):
        out, vjp = jax.vjp(lambda *a: ssd(*a, 128), x, dt, a_log, b, c, d,
                           dt_bias)
        return out, vjp(out)
    compiled = (jax.jit(f).trace(x, dt, head, bc, bc, head, head)
                .lower(lowering_platforms=("tpu",)).compile())
    assert not kernel_calls(compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes


def test_flash_at_the_cell_s_grouped_heads():
    """32 query heads over 2 key/value heads of 128 (16 a group), causal,
    8,192 rows, head-major as the layer hands them over."""
    from caffe_mpi_tpu.ops.flash_attention import flash_attention_heads
    q = on_chip((1, 32, 8192, 128), jnp.bfloat16)
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
    driver = harness.load_module(bench / "drivers" / "train_ssm_lm.py")
    cell = harness.load_cell("nemotron3_nano_bf16_s8k_ep16share",
                             rehearse=False)
    pattern = cell["config"]["hybrid_override_pattern"]
    job = driver.train.build_job(
        cell, 0, Path(os.environ.get("TMPDIR", "/tmp")) / "aot_ssm_lm")
    solver = job.solver
    try:
        assert not solver._guard_on   # static loss scale: one state
        rep = SingleDeviceSharding(v5e_devices()[0])
        feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
                 for k, (shape, _) in solver.net.feed_specs.items()}
        assert {k: v.shape for k, v in feeds.items()} == {
            "tokens": (1, 8192), "label": (1, 8192)}
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
    assert mem.argument_size_in_bytes >= 12 * 666_963_456
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # the pattern's first nine layers fit under the configuration's limit
    # with the Mamba-2 layers' `remat: true`: 13.69e9 with it, keeping
    # each layer's input product, scan output and carried states (12.30e9
    # keeping none, PR 40's tree with `row_bound` 2.5; 12.07e9 with 1.5),
    # 15.61e9 with no layer's `remat` (PERF.md section 4, PR 41)
    assert pattern == "MEMEM*EME" and live < 14.5e9, live
    calls, fallback = step_calls(compiled.as_text())
    flash = [c for c in calls if c.startswith("flash_")]
    # remat does not run the forward kernel a second time
    assert sorted(flash) == ["flash_dkv", "flash_dq", "flash_fwd"]
    expected = cell["config"]["checks"]["pallas_calls_per_step"]["bf16"]
    experts = pattern.count("E")
    # an ungated expert: 2 gmm forward, 2 gmm and 2 tgmm backward a layer
    assert len(calls) == expected == 3 + 6 * experts, (len(calls), expected)
    # and in the branch a balanced router never takes, XLA's own
    assert fallback.count("ragged-dot-none") == experts * 7, fallback


def products_by_scope(text: str) -> dict[tuple[str, str], int]:
    """`dot` and `convolution` instructions of a compiled module, by the
    `ssm.*` scope their op_name names ("" outside one) and by pass: a
    backward instruction's op_name holds `transpose(`."""
    counts: dict[tuple[str, str], int] = {}
    for line in text.splitlines():
        if not re.search(r"= \S+ (convolution|dot)\(", line):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        name = name.group(1) if name else ""
        scope = re.search(r"ssm\.\w+", name)
        key = (scope.group(0) if scope else "",
               "backward" if "transpose(" in name else "forward")
        counts[key] = counts.get(key, 0) + 1
    return counts


def test_remat_keeps_what_a_mamba2_layer_s_backward_pass_reads():
    """The tiny recipe's forward and backward pass, bf16, compiled for one
    described v5e: under `remat: true` a Mamba2 layer keeps its input
    product's result, its scan's output and the scan's carried states
    (`Mamba2Layer.kept_under_remat`). So the input product runs once
    forward and twice backward (dX, dW), not again to recompute the layer;
    the scan's forward products run once in the backward pass, inside
    ops/ssd.py's own checkpoint, not a second time for the layer's. The
    attention layer's `remat` keeps the flash kernel's output as before.
    Take any one name off the policy and a count here moves."""
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.ops.ssd import ssd
    from caffe_mpi_tpu.proto import NetParameter
    recipe = ROOT / "models" / "nemotron3_nano_30b_a3b" / "tiny_train_val.prototxt"
    net = Net(NetParameter.from_text(recipe.read_text()), phase="TRAIN",
              precision="bf16")
    mixers = [l for l in net.layers if l.type_name == "Mamba2"]
    assert mixers and all(l.lp.remat for l in mixers)
    rep = SingleDeviceSharding(v5e_devices()[0])
    params, state = net.init(jax.random.PRNGKey(0))
    feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
             for k, (shape, _) in net.feed_specs.items()}

    def step(p, feeds):
        return jax.value_and_grad(lambda p: net.apply(
            p, state, feeds, train=True, rng=None)[2])(p)
    text = compile_tpu(step, abstract(params, rep), feeds)
    got = products_by_scope(text)

    # the scan alone at the layers' sizes, under its own checkpoint
    p, (n, s, _) = mixers[0].p, mixers[0].in_shapes[0]
    x = on_chip((n, s, p.num_heads, p.head_dim), jnp.bfloat16)
    dt = on_chip((n, s, p.num_heads), jnp.bfloat16)
    bc = on_chip((n, s, p.groups, p.state_size), jnp.bfloat16)
    head = on_chip((p.num_heads,), jnp.float32)

    def scan(*args):
        out, vjp = jax.vjp(lambda *a: ssd(*a, p.chunk), *args)
        return vjp(out)
    alone = products_by_scope(compile_tpu(scan, x, dt, head, bc, bc, head,
                                          head))
    layers = len(mixers)
    assert got[("ssm.project", "forward")] == layers
    assert got[("ssm.project", "backward")] == 2 * layers
    assert got[("ssm.scan", "forward")] == layers * alone[("", "forward")]
    assert got[("ssm.scan", "backward")] == layers * alone[("", "backward")]
    flash = [c for c in step_calls(text)[0] if c.startswith("flash_")]
    assert sorted(flash) == ["flash_dkv", "flash_dq", "flash_fwd"]
