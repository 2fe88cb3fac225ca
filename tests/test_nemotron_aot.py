"""The benchmark's state-space cell (nemotron3_nano_bf16_s8k_ep16share) at
its published widths, compiled for a described v5e with no chip present
(tests/test_tpu_aot_compile.py has the helpers and the other cells): the
chunked scan with its backward pass, the flash kernels at its head counts,
the ungated grouped products, and the whole train step as the
`train_ssm_lm` driver builds it, whose bytes chose the depth."""

import os
import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from test_tpu_aot_compile import (_glue_bytes_tool, abstract, bench_harness,
                                  compile_tpu, kernel_calls, on_chip,
                                  step_calls, v5e_devices)

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


def test_one_mamba2_layer_s_bytes():
    """One Mamba-2 layer of the cell's recipe (`blk0/ssm`), forward +
    backward under its `remat`, by its five scopes
    (`tools/attention_glue_bytes.py`: operand + result bytes of the
    compiled passes). Held at what ops/ssd.py's hand-written backward rule
    and its tile-moving views reached plus a tenth (17.32 GB a layer, 6.46
    of them under `ssm.scan`, with jax's own backward pass under a
    checkpoint and plain transposes): a forward pass run again or a
    float32 tensor of x's size moved between layouts shows here, at no
    chip time."""
    tool = _glue_bytes_tool()
    records = tool.layer_records(
        str(ROOT / "models/nemotron3_nano_30b_a3b/train_val.prototxt"),
        "blk0/ssm", "bf16", v5e_devices()[0])
    totals = tool.totals(records)
    assert totals["ssm.scan"] <= 1.1 * 4.79e9, totals
    assert sum(totals.values()) <= 1.1 * 13.29e9, totals
    # under `ssm.scan` only a product writes float32 of x's size
    scan = [r for r in records if r["kind"] == "ssm.scan"]
    assert tool.widest_f32_glue_result(scan) < 8192 * 64 * 64


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
    # with the Mamba-2 layers' `remat: true`: 13.71e9 with it, keeping
    # each layer's input product, scan output, and the carried states and
    # two vectors its scan's backward rule reads (13.69e9 with jax's own
    # backward pass of the scan; 12.30e9 keeping none with `row_bound`
    # 2.5, 12.07e9 with 1.5), 15.61e9 with no layer's `remat` (PERF.md
    # section 4)
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


def products(text: str) -> list[tuple[str, str, str]]:
    """`dot` and `convolution` instructions of a compiled module, one
    (scope, pass, label) record each: the `ssm.*` scope their op_name names
    ("" outside one), the pass (a backward instruction's op_name holds
    `transpose(`), and the op_name's last scope, which for a product
    `jnp.einsum` wrote is its equation. XLA may merge two equal products of
    one program into one instruction, never split one into two."""
    out = []
    for line in text.splitlines():
        if not re.search(r"= \S+ (convolution|dot)\(", line):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        name = name.group(1) if name else ""
        scope = re.search(r"ssm\.\w+", name)
        out.append((scope.group(0) if scope else "",
                    "backward" if "transpose(" in name else "forward",
                    name.rsplit("/", 2)[-2] if "/" in name else name))
    return out


def products_by_scope(text: str) -> Counter:
    """`products` counted by scope and pass."""
    return Counter((scope, side) for scope, side, _ in products(text))


def einsums(text: str, scope: str, side: str) -> Counter:
    """`products` under `scope` in the pass `side`, counted by label."""
    return Counter(label for s, p, label in products(text)
                   if (s, p) == (scope, side))


def test_remat_keeps_what_a_mamba2_layer_s_backward_pass_reads():
    """The tiny recipe's forward and backward pass, bf16, compiled for one
    described v5e: under `remat: true` a Mamba2 layer keeps its input
    product's result, its scan's output and what the scan's backward rule
    reads (`Mamba2Layer.kept_under_remat`: the carried states and two
    vectors, `ssd.KEPT`). So the input product runs once forward and twice
    backward (dX, dW), not again to recompute the layer; the scan's
    forward products run once a layer in the whole step, and its backward
    pass runs ops/ssd.py's hand-written rule's products alone, each once a
    layer (of the forward's, only C B^T and the read-out S_in C, which the
    rule builds again by design), every operation of it under `ssm.scan`
    and `transpose(`. The
    attention layer's `remat` keeps the flash kernel's output as before.
    Take any one name off the policy and a count here moves."""
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.ops.ssd import ssd
    from caffe_mpi_tpu.proto import NetParameter
    sys.path[:0] = [p for p in (str(ROOT / "tools"),) if p not in sys.path]
    from attention_glue_bytes import SSM_SCOPES, classify
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

    # the scan alone at the layers' sizes: its forward pass, and its
    # forward and backward passes (the backward rule opens `ssm.scan`)
    p, (n, s, _) = mixers[0].p, mixers[0].in_shapes[0]
    x = on_chip((n, s, p.num_heads, p.head_dim), jnp.bfloat16)
    dt = on_chip((n, s, p.num_heads), jnp.bfloat16)
    bc = on_chip((n, s, p.groups, p.state_size), jnp.bfloat16)
    head = on_chip((p.num_heads,), jnp.float32)
    args = (x, dt, head, bc, bc, head, head)

    def scan(dy, *args):
        out, vjp = jax.vjp(lambda *a: ssd(*a, p.chunk), *args)
        return out, vjp(dy)
    forward_text = compile_tpu(lambda *a: ssd(*a, p.chunk), *args)
    both_text = compile_tpu(scan, x, *args)
    assert set(products_by_scope(forward_text)) == {("", "forward")}
    assert set(products_by_scope(both_text)) == {("", "forward"),
                                                 ("ssm.scan", "backward")}
    forward = einsums(forward_text, "", "forward")
    rule = einsums(both_text, "ssm.scan", "backward")
    # no forward product of the scan runs again for its backward pass
    assert einsums(both_text, "", "forward") == forward
    ops = [r for r in classify(both_text, SSM_SCOPES)
           if r["kind"] == "ssm.scan" or r["pass"] == "backward"]
    assert ops and all(r["kind"] == "ssm.scan" and r["pass"] == "backward"
                       for r in ops), ops
    # of the forward's products the rule computes none again but C B^T
    # (M is built again from it) and the read-out S_in C (its product with
    # dy is the running sums' gradient from the read-out), which XLA takes
    # from the forward pass where the two passes are one program: not the
    # intra-chunk product, the chunks' end states, the carried states, and
    # of the two running sums' triangles one runs backward, dl's transposed
    cb, read_out = "ncgsi,ncgsj->ncgij", "ncgrps,ncgsi->ncgrpi"
    assert forward[cb] == forward[read_out] == 1, forward
    assert not {e for e in forward if e != "...j,ij->...i"} & set(rule), rule
    assert (forward["...j,ij->...i"], rule["...j,ij->...i"]) == (2, 1), rule

    layers = len(mixers)
    per_layer = lambda c: Counter({k: layers * v for k, v in c.items()})
    got = products_by_scope(text)
    assert got[("ssm.project", "forward")] == layers
    assert got[("ssm.project", "backward")] == 2 * layers
    # in the step, a layer's forward products once, and its backward pass
    # the rule's products and those two, each once
    assert einsums(text, "ssm.scan", "forward") == per_layer(forward)
    assert einsums(text, "ssm.scan", "backward") == per_layer(
        rule + Counter([cb, read_out]))
    flash = [c for c in step_calls(text)[0] if c.startswith("flash_")]
    assert sorted(flash) == ["flash_dkv", "flash_dq", "flash_fwd"]
