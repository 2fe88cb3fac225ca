"""Deviceless TPU v5e compile of everything this tree hands to Mosaic.

The installed libtpu compiles for a DESCRIBED topology with no chip
present: `get_topology_desc("v5e:2x2")` gives four abstract `TPU v5 lite`
devices, and `jit(f).trace(*abstract_args).lower(lowering_platforms=
("tpu",)).compile()` runs the real XLA:TPU + Mosaic pipeline against
them. Interpret mode on the 8 virtual CPU devices cannot see what this
sees: Mosaic's tiling/alignment proofs, its scoped-VMEM limit, and the
GSPMD partitioner's refusal of Mosaic calls.

Shapes are the ones the shipped models use (AlexNet norm1/norm2,
models/transformer_lm's S=64 4x32 heads) plus the long-sequence cases.
Nothing here executes; a pass means "compiles", numbers come from
chip_smoke.py's `kernels` leg on the chip.
"""

import functools
import importlib.util
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.skipif(
    importlib.util.find_spec("libtpu") is None,
    reason="libtpu not installed: no TPU compiler to compile against")


@functools.cache
def v5e_devices():
    """Four abstract v5e devices. Any failure past the libtpu import is
    a test failure, not a skip."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def tpu_mesh(data: int, model: int) -> Mesh:
    devs = np.array(v5e_devices()[:data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def compile_tpu(fn, *args) -> str:
    """Compiled-module text of `fn` for the abstract v5e devices the
    args' shardings name."""
    return (jax.jit(fn).trace(*args)
            .lower(lowering_platforms=("tpu",)).compile().as_text())


def on_chip(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(
                                    v5e_devices()[0]))


def mosaic_calls(text: str) -> int:
    return text.count("tpu_custom_call")


def kernel_calls(text: str) -> list[tuple[str, list[str]]]:
    """(name, operand names) of each Mosaic call in a compiled module's
    text, `%` stripped: `("lrn_fwd.2", ["bitcast.9"])`."""
    return [(name, re.findall(r"%([\w.-]+)", operands))
            for name, operands in re.findall(
                r"%([\w.-]+) = [^\n]*? custom-call\(([^)]*)\), "
                r'custom_call_target="tpu_custom_call"', text)]


def bench_harness():
    """`benchmarks/run.py`, loaded by its path: under the bare name `run`
    an example's `run.py` may already stand in `sys.modules` (whichever
    test file the worker imported first decided it)."""
    from pathlib import Path
    path = Path(__file__).resolve().parent.parent / "benchmarks" / "run.py"
    spec = importlib.util.spec_from_file_location("bench_run", path)
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    return harness


def step_calls(text: str) -> tuple[list[str], list[str]]:
    """Kernel names of a compiled step's Mosaic calls: this tree's own,
    which a step runs, and XLA:TPU's `ragged-dot` kernels, which only the
    expert layers' `moe.fallback` branches hold (they run where held
    experts received more rows than their layer's bound; a traced run
    counts executed calls: the first list)."""
    names = [re.sub(r"\.\d+$", "", name) for name, _ in kernel_calls(text)]
    return ([n for n in names if not n.startswith("ragged-dot")],
            [n for n in names if n.startswith("ragged-dot")])


class TestInterpretOnlyOnCpu:
    """ops/pallas_call.py: the interpreter is the cpu platform's and
    nobody else's."""

    def _lowered(self, platform):
        from caffe_mpi_tpu.ops.lrn import lrn_across_channels
        x = jax.ShapeDtypeStruct((2, 8, 4, 4), jnp.bfloat16)
        f = lambda x: lrn_across_channels(x, 5, 1e-4, 0.75, 1.0)
        return jax.jit(f).trace(x).lower(
            lowering_platforms=(platform,)).as_text()

    def test_cpu_lowering_has_no_mosaic_call(self):
        assert "tpu_custom_call" not in self._lowered("cpu")

    def test_tpu_lowering_is_mosaic_under_a_cpu_default_backend(self):
        assert jax.default_backend() == "cpu"
        assert "tpu_custom_call" in self._lowered("tpu")

    def test_other_platform_never_gets_the_interpreter(self):
        # cuda is not cpu: it must take the compiled branch. This
        # installation has no GPU Pallas backend, so it fails — which is
        # the contract ("compiles or fails"); an interpreted lowering
        # would succeed silently
        with pytest.raises(Exception):
            self._lowered("cuda")


class TestLRNKernels:
    # batch a multiple of 128: the (H*W, C, N) view; below it the
    # (N, C, H*W) view with the whole spatial extent in a block
    @pytest.mark.parametrize("shape", [
        (1024, 96, 55, 55), (1024, 256, 27, 27),    # the benchmark's batch
        (256, 96, 55, 55), (256, 256, 27, 27),      # the recipe's
        (2, 96, 55, 55), (2, 256, 27, 27),
        (32, 64, 56, 56),                           # GoogLeNet fp16
        (1, 96, 55, 55),                            # a serving bucket
    ], ids=lambda s: "x".join(map(str, s)))
    def test_fwd_bwd_compile_bf16(self, shape):
        from caffe_mpi_tpu.ops.lrn import lrn_across_channels

        def fwd_bwd(x):
            f = lambda x: lrn_across_channels(x, 5, 1e-4, 0.75, 1.0)
            y, vjp = jax.vjp(f, x)
            return y, vjp(y)[0]
        text = compile_tpu(fwd_bwd, on_chip(shape, jnp.bfloat16))
        # the kernels carry their own names into the HLO, and so into a
        # profiler trace (they used to read `branch_0_fun.N`); one call
        # per direction
        assert sorted(name.split(".")[0]
                      for name, _ in kernel_calls(text)) == [
            "lrn_bwd", "lrn_fwd"]

    def test_bf16_lrn_takes_the_convolution_layout(self):
        """conv -> relu -> LRN -> max-pool and its gradient at AlexNet's
        norm1 shape: XLA holds these activations batch-minor, the
        kernels read and write that order, so every operand of theirs is
        a bitcast and no pad or copy is filed under the LRN layer."""
        from caffe_mpi_tpu.net import Net
        from caffe_mpi_tpu.proto import NetParameter
        net = Net(NetParameter.from_text(_ALEXNET_HEAD % 1024),
                  phase="TRAIN", precision="bf16")
        params, state = net.init(jax.random.PRNGKey(0))

        def grads(p, s, x):
            return jax.grad(lambda p: jnp.sum(net.apply(
                p, s, {"data": x}, train=True,
                rng=jax.random.PRNGKey(0))[0]["pool1"].astype(jnp.float32)))(p)
        sh = SingleDeviceSharding(v5e_devices()[0])
        text = compile_tpu(grads, abstract(params, sh), abstract(state, sh),
                           on_chip((1024, 3, 227, 227), jnp.float32))
        calls = kernel_calls(text)
        assert sorted(name.split(".")[0] for name, _ in calls) == [
            "lrn_bwd", "lrn_fwd"]
        for name, operands in calls:
            assert all(o.startswith("bitcast") for o in operands), (
                name, operands)
        moved = [line.split("=")[0].strip() for line in text.splitlines()
                 if re.search(r" (copy|pad)\(", line)
                 and "caffe.LRN.norm1" in line]
        assert not moved, moved


class TestFlashKernels:
    @staticmethod
    def _fwd_bwd(causal):
        from caffe_mpi_tpu.ops.flash_attention import flash_attention

        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=causal), q, k, v)
            return out, vjp(out)
        return f

    @pytest.mark.parametrize("s,h,d,dtype", [
        (64, 4, 32, jnp.float32),      # models/transformer_lm
        (1024, 2, 128, jnp.bfloat16),
        (1000, 2, 64, jnp.float32),    # padded + masked tail
    ], ids=["lm-s64-d32", "s1024-d128-bf16", "s1000-d64"])
    def test_causal_fwd_bwd_compile(self, s, h, d, dtype):
        arg = on_chip((2, s, h, d), dtype)
        text = compile_tpu(self._fwd_bwd(True), arg, arg, arg)
        assert mosaic_calls(text) >= 3  # fwd, dQ, dK/dV

    @pytest.mark.parametrize("s", [8192, 24576])
    def test_long_f32_forward_raises_its_vmem_limit(self, s):
        """Whole-sequence K and V blocks: at S=24576 d=128 f32 this
        libtpu reports "Scoped allocation with size 48.00M and limit
        16.00M" unless the kernel asks for more (ISSUE 21 saw the same
        RESOURCE_EXHAUSTED at S=8192)."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        arg = on_chip((2, s, 2, 128), jnp.float32)
        text = compile_tpu(lambda q, k, v: flash_attention(q, k, v),
                           arg, arg, arg)
        assert mosaic_calls(text) >= 1

    def test_sequence_past_vmem_is_a_typed_error(self):
        from caffe_mpi_tpu.ops.flash_attention import (FlashVmemError,
                                                       flash_attention)
        arg = on_chip((1, 65536, 1, 128), jnp.float32)
        with pytest.raises(FlashVmemError, match=r"65536.*MiB"):
            compile_tpu(lambda q, k, v: flash_attention(q, k, v),
                        arg, arg, arg)

    def test_ring_flash_compiles_under_shard_map_on_four_chips(self):
        from caffe_mpi_tpu.ops.attention import sequence_parallel_attention
        mesh = tpu_mesh(1, 4)
        sh = NamedSharding(mesh, P(None, "model", None, None))
        arg = jax.ShapeDtypeStruct((2, 512, 2, 64), jnp.float32,
                                   sharding=sh)

        def f(q, k, v):
            attn = lambda q, k, v: sequence_parallel_attention(
                q, k, v, mesh, seq_axis="model", causal=True,
                use_flash=True)
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(out)
        text = compile_tpu(f, arg, arg, arg)
        assert mosaic_calls(text) >= 3
        assert "collective-permute" in text


class TestSmallThinkerCell:
    """The benchmark's language-model cell (smallthinker_bf16_s8k_ep4share)
    at its published widths: the flash kernels with a window and grouped
    key/value heads, and the whole train step as the `train_lm` driver
    builds it. `benchmarks/aot_check.py` feeds float32 to every 2-D input,
    which would send token ids through the compute type, so the cell's
    deviceless compile lives here."""

    @pytest.mark.parametrize("window", [0, 4096], ids=["global", "w4096"])
    def test_flash_window_grouped_heads_at_the_cell_shape(self, window):
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q = on_chip((1, 8192, 28, 128), jnp.bfloat16)
        kv = on_chip((1, 8192, 4, 128), jnp.bfloat16)

        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window), q, k, v)
            return out, vjp(out)
        text = compile_tpu(f, q, kv, kv)   # Mosaic refuses what VMEM lacks
        names = sorted(re.sub(r"\.\d+$", "", name)
                       for name, _ in kernel_calls(text))
        assert names == ["flash_dkv", "flash_dq", "flash_fwd"]
        # K and V are read per key/value head, never repeated to 28
        assert "bf16[28,8192,128]" in text
        assert not re.search(r"bf16\[1,8192,28,128\][^\n]* broadcast", text)

    def test_whole_step_compiles_and_fits_the_chip(self):
        import os
        import sys
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        bench = root / "benchmarks"
        sys.path[:0] = [p for p in (str(bench),) if p not in sys.path]
        harness = bench_harness()
        driver = harness.load_module(bench / "drivers" / "train_lm.py")
        cell = harness.load_cell("smallthinker_bf16_s8k_ep4share",
                                 rehearse=False)
        job = driver.train.build_job(
            cell, 0, Path(os.environ.get("TMPDIR", "/tmp")) / "aot_lm")
        solver = job.solver
        try:
            assert not solver._guard_on   # static loss scale: one state
            rep = SingleDeviceSharding(v5e_devices()[0])
            feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=rep)
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
        assert mem.argument_size_in_bytes >= 12 * 656_529_920
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        # the chip's allocator has 16.909e9 bytes (`bytes_limit`). 12.54e9
        # here since the expert layers keep row-bounded buffers for the
        # backward pass (PERF.md, PR 32); 13.26e9 before, 13.24e9 at the
        # run's peak on the chip (PERF.md, PR 28); it was 15.88e9 / 15.32e9
        # while the loss kept a float32 log-softmax for its backward pass
        # and jax's transpose of its label pick zero-filled a second
        # buffer of the logits' size (PR 27)
        assert live < 13.0e9, live
        calls, fallback = step_calls(compiled.as_text())
        flash = [c for c in calls if c.startswith("flash_")]
        assert sorted(flash) == (["flash_dkv"] * 4 + ["flash_dq"] * 4
                                 + ["flash_fwd"] * 4)
        expected = cell["config"]["checks"]["pallas_calls_per_step"]["bf16"]
        assert len(calls) == expected, (len(calls), expected)
        # four expert layers' other branch: three products forward, in the
        # backward pass those again (the last feeds only the frozen
        # router's gradient and goes) and their six transposes
        assert fallback.count("ragged-dot-none") == 4 * 11, fallback


class TestJoyAICell:
    """The benchmark's latent-attention cell (joyai_flash_bf16_s8k_epshare)
    at its published widths: the flash kernels with a 192-wide query/key
    operand beside 128-wide values, and the whole train step as the
    `train_mla_lm` driver builds it."""

    def test_flash_at_unequal_widths_at_the_cell_shape(self):
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        qk = on_chip((1, 8192, 32, 192), jnp.bfloat16)
        v = on_chip((1, 8192, 32, 128), jnp.bfloat16)

        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True), q, k, v)
            return out, vjp(out)
        text = compile_tpu(f, qk, qk, v)   # Mosaic refuses what VMEM lacks
        names = sorted(re.sub(r"\.\d+$", "", name)
                       for name, _ in kernel_calls(text))
        assert names == ["flash_dkv", "flash_dq", "flash_fwd"]
        # one 192-wide operand, not padded to 256 by the wrapper
        assert "bf16[32,8192,192]" in text
        assert "bf16[32,8192,256]" not in text

    def test_whole_step_compiles_and_fits_the_chip(self):
        import os
        import sys
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        bench = root / "benchmarks"
        sys.path[:0] = [p for p in (str(bench),) if p not in sys.path]
        harness = bench_harness()
        driver = harness.load_module(bench / "drivers" / "train_mla_lm.py")
        cell = harness.load_cell("joyai_flash_bf16_s8k_epshare",
                                 rehearse=False)
        job = driver.train.build_job(
            cell, 0, Path(os.environ.get("TMPDIR", "/tmp")) / "aot_mla_lm")
        solver = job.solver
        try:
            assert not solver._guard_on   # static loss scale: one state
            rep = SingleDeviceSharding(v5e_devices()[0])
            feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=rep)
                     for k, (shape, _) in solver.net.feed_specs.items()}
            assert {k: v.shape for k, v in feeds.items()} == {
                k: (1, 8192) for k in ("tokens", "label", "label_mtp")}
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
        assert mem.argument_size_in_bytes >= 12 * 680_441_088
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        # the chip's allocator has 16.909e9 bytes. 12.78e9 here since the
        # expert layers keep row-bounded buffers for the backward pass
        # (PERF.md, PR 32); 14.68e9 before, with the six attention layers'
        # `remat: true` (their flash outputs kept, the projections
        # computed again); 16.29e9 without it, 16.53e9 with the norms
        # computed again as well (PERF.md, PR 31)
        assert live < 13.4e9, live
        calls, fallback = step_calls(compiled.as_text())
        flash = [c for c in calls if c.startswith("flash_")]
        # remat does not run the forward kernel a second time
        assert sorted(flash) == (["flash_dkv"] * 6 + ["flash_dq"] * 6
                                 + ["flash_fwd"] * 6)
        expected = cell["config"]["checks"]["pallas_calls_per_step"]["bf16"]
        assert len(calls) == expected == 63, (len(calls), expected)
        assert fallback.count("ragged-dot-none") == 5 * 11, fallback


class TestSdarCell:
    """The benchmark's block-diffusion cell (sdar_bf16_s8k_bd4_ep8share) at
    its published widths: the flash kernels under the block mask over the
    2 x 8,192 rows of [noisy | clean] with grouped heads, and the whole
    train step as the `train_bd_lm` driver builds it."""

    @pytest.mark.parametrize("block", [4, 3, 1024],
                             ids=["cell", "no_power_of_two", "over_a_tile"])
    def test_flash_under_the_block_mask_at_the_cell_shape(self, block):
        """The mask's integer division of a row and a column of positions
        by the block length lowers whatever the length; a block longer
        than a tile brings the runs of inside tiles among the noisy."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q = on_chip((1, 16384, 32, 128), jnp.bfloat16)
        kv = on_chip((1, 16384, 4, 128), jnp.bfloat16)

        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, block_diffusion=block), q, k, v)
            return out, vjp(out)
        text = compile_tpu(f, q, kv, kv)   # Mosaic refuses what VMEM lacks
        names = sorted(re.sub(r"\.\d+$", "", name)
                       for name, _ in kernel_calls(text))
        assert names == ["flash_dkv", "flash_dq", "flash_fwd"]
        assert "bf16[32,16384,128]" in text

    def test_whole_step_compiles_and_fits_the_chip(self):
        import os
        import sys
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        bench = root / "benchmarks"
        sys.path[:0] = [p for p in (str(bench),) if p not in sys.path]
        harness = bench_harness()
        driver = harness.load_module(bench / "drivers" / "train_bd_lm.py")
        cell = harness.load_cell("sdar_bf16_s8k_bd4_ep8share",
                                 rehearse=False)
        job = driver.train.build_job(
            cell, 0, Path(os.environ.get("TMPDIR", "/tmp")) / "aot_bd_lm")
        solver = job.solver
        try:
            assert not solver._guard_on   # static loss scale: one state
            rep = SingleDeviceSharding(v5e_devices()[0])
            feeds = {k: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=rep)
                     for k, (shape, _) in solver.net.feed_specs.items()}
            assert {k: v.shape for k, v in feeds.items()} == {
                "tokens": (1, 8192)}
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
        assert mem.argument_size_in_bytes >= 12 * 550_984_960
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        # 13.96e9 with the five attention layers' `remat: true` (their
        # flash outputs kept); 15.55e9 as written, 16.25e9 at six layers
        # even so (PERF.md, PR 33): the configuration's limit is 14.5e9
        assert live < 14.5e9, live
        calls, fallback = step_calls(compiled.as_text())
        flash = [c for c in calls if c.startswith("flash_")]
        # remat does not run the forward kernel a second time
        assert sorted(flash) == (["flash_dkv"] * 5 + ["flash_dq"] * 5
                                 + ["flash_fwd"] * 5)
        expected = cell["config"]["checks"]["pallas_calls_per_step"]["bf16"]
        assert len(calls) == expected == 60, (len(calls), expected)
        assert fallback.count("ragged-dot-none") == 5 * 11, fallback


# (model, layer, glue GB, all three classes GB, rows x heads x head_dim) as
# PR 34 left them (PERF.md section 5); before it 11.92 / 15.44, 7.11 /
# 10.65, 3.25 / 4.88, 1.41 / 3.04
_GLUE_CASES = {
    "sdar_block_mask_qk_norm": ("sdar_30b_a3b", "blk0/attn", 3.48, 8.89,
                                16384 * 32 * 128),
    "joyai_latent": ("joyai_llm_flash", "blk0/attn", 2.67, 7.01,
                     8192 * 32 * 128),
    "smallthinker_window_rotary": ("smallthinker_21b_a3b", "blk1/attn",
                                   1.13, 3.33, 8192 * 28 * 128),
    "smallthinker_full": ("smallthinker_21b_a3b", "blk0/attn", 1.28, 3.18,
                          8192 * 28 * 128),
    # PR 38, as it left it: 4.57 in all with the mixing along the sequence
    # in float32 between its fusions
    "zaya_cca": ("zaya1_8b", "blk0/attn", 2.05, 3.39, 8192 * 8 * 128),
}


class TestAttentionGlueBytes:
    """What one attention layer of each language-model cell moves through
    HBM beside its kernels and its matrix products
    (`tools/attention_glue_bytes.py`: operand + result bytes of the
    compiled forward + backward pass, under the layer's own `remat`). A
    count from the compiler's text, held at what PR 34 reached plus a
    tenth: a float32 tensor of q's size, a second layout of it or a lane
    rotation that became a pass of its own again shows here, at no chip
    time."""

    @pytest.mark.parametrize("case", list(_GLUE_CASES))
    def test_one_layer_forward_and_backward(self, case):
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        spec = importlib.util.spec_from_file_location(
            "attention_glue_bytes", root / "tools/attention_glue_bytes.py")
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        model, name, glue_gb, all_gb, q_elements = _GLUE_CASES[case]
        records = tool.layer_records(
            str(root / "models" / model / "train_val.prototxt"), name,
            "bf16", v5e_devices()[0])
        totals = tool.totals(records)
        assert sum(r["kind"] == "mosaic" for r in records) == 3
        assert totals["glue"] <= 1.1 * glue_gb * 1e9, totals
        assert sum(totals.values()) <= 1.1 * all_gb * 1e9, totals
        # no glue operation writes a float32 tensor of q's size (the
        # widest left is a parameter's gradient)
        assert tool.widest_f32_glue_result(records) < q_elements


_ALEXNET_HEAD = """
name: "alexnet_head"
layer { name: "data" type: "Input" top: "data"
        input_param { shape { dim: %d dim: 3 dim: 227 dim: 227 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 96 kernel_size: 11 stride: 4
                            weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool1" type: "Pooling" bottom: "norm1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""


_ALEXNET_NORM2 = """
name: "alexnet_norm2"
layer { name: "data" type: "Input" top: "pool1"
        input_param { shape { dim: %d dim: 96 dim: 27 dim: 27 } } }
layer { name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
        convolution_param { num_output: 256 pad: 2 kernel_size: 5 group: 2
                            weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "relu2" type: "ReLU" bottom: "conv2" top: "conv2" }
layer { name: "norm2" type: "LRN" bottom: "conv2" top: "norm2"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "pool2" type: "Pooling" bottom: "norm2" top: "pool2"
        pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
"""


class TestLaxLRN:
    """ops/lrn_lax.py, the f32 across-channels LRN: conv -> relu -> LRN
    -> max-pool and its gradient at AlexNet's two shapes, batch 1024 (the
    benchmark's `alexnet_f32` cells). A count, never a time: the times
    are PERF.md section 6's (PR 39)."""

    # scoped_ops: device operations of the entry computation filed under
    # the layer's scope by the form that shipped (PR 39): the forward's
    # fusion and the backward's window product (dx rides in the
    # convolution's backward fusions); the form before it had three
    @pytest.mark.parametrize("net_text,feed,in_shape,top,scoped_ops", [
        (_ALEXNET_HEAD, "data", (1024, 3, 227, 227), "pool1", 2),
        (_ALEXNET_NORM2, "pool1", (1024, 96, 27, 27), "pool2", 2),
    ], ids=["norm1", "norm2"])
    def test_f32_fwd_bwd_is_fusions_around_a_product(self, net_text, feed,
                                                     in_shape, top,
                                                     scoped_ops):
        from caffe_mpi_tpu.net import Net
        from caffe_mpi_tpu.proto import NetParameter
        net = Net(NetParameter.from_text(net_text % 1024), phase="TRAIN")
        params, state = net.init(jax.random.PRNGKey(0))

        def grads(p, s, x):
            return jax.grad(lambda p, x: jnp.sum(net.apply(
                p, s, {feed: x}, train=True,
                rng=jax.random.PRNGKey(0))[0][top] ** 2), (0, 1))(p, x)
        sh = SingleDeviceSharding(v5e_devices()[0])
        text = compile_tpu(grads, abstract(params, sh), abstract(state, sh),
                           on_chip(in_shape, jnp.float32))
        assert mosaic_calls(text) == 0
        scoped = [line for line in text.splitlines() if "caffe.LRN." in line]
        assert scoped
        assert not [line for line in scoped if " power(" in line]
        entry = text[text.index("\nENTRY "):].splitlines()
        filed = [line.split("=")[0].strip() for line in entry
                 if "caffe.LRN." in line
                 and not re.search(r" (get-tuple-element|bitcast)\(", line)]
        assert len(filed) <= scoped_ops, filed

    def test_conv_lrn_weight_gradient_compiles_at_an_odd_batch(self):
        """conv1 -> relu -> LRN and conv1's weight gradient at batch 4,
        where the band product spelled as an einsum (a dot_general) kept
        XLA:TPU compiling for 210 s (3, 5, 6, 10, 12: 94-207 s; PERF.md
        section 6, PR 39). As a 1x1 convolution it is seconds; no time is
        asserted here, only the spelling and that it compiles."""
        from caffe_mpi_tpu.ops.lrn_lax import lrn_across_channels
        lrn = lambda x: lrn_across_channels(x, 5, 1e-4, 0.75, 1.0)
        lowered = jax.jit(lrn).lower(
            jax.ShapeDtypeStruct((4, 96, 55, 55), jnp.float32)).as_text()
        assert "stablehlo.convolution" in lowered
        assert "dot_general" not in lowered

        def dw(w, x):
            y = jax.lax.conv_general_dilated(x, w, (4, 4), "VALID")
            return jnp.sum(lrn(jnp.maximum(y, 0.0)) ** 2)
        compile_tpu(jax.grad(dw), on_chip((96, 3, 11, 11), jnp.float32),
                    on_chip((4, 3, 227, 227), jnp.float32))


class TestServingBuckets:
    """The f32 deploy path holds no Pallas call, and still met a
    compiler refusal: with the across-channels LRN written as a padded
    lax.reduce_window, XLA:TPU (libtpu 0.0.34) rejected AlexNet's serving
    buckets 1 and 4 — "Binary op with incompatible shapes:
    f32[55,8,8,96] and f32[55,8,8,92]" (bucket 10 and the b256 train
    step compiled). chip_smoke.py's serve leg found it on the chip; this
    is its deviceless regression."""

    @pytest.mark.parametrize("batch", [1, 4, 10, 256])
    def test_alexnet_head_compiles_at_small_batches(self, batch):
        from caffe_mpi_tpu.net import Net
        from caffe_mpi_tpu.proto import NetParameter
        net = Net(NetParameter.from_text(_ALEXNET_HEAD % batch),
                  phase="TEST")
        params, state = net.init(jax.random.PRNGKey(0))
        sh = SingleDeviceSharding(v5e_devices()[0])
        compile_tpu(
            lambda p, s, x: net.apply(p, s, {"data": x},
                                      train=False)[0]["pool1"],
            abstract(params, sh), abstract(state, sh),
            on_chip((batch, 3, 227, 227), jnp.float32))


_CONV_LRN_NET = """
name: "conv_lrn"
layer { name: "data" type: "Input" top: "data" top: "label"
        input_param { shape { dim: 16 dim: 3 dim: 32 dim: 32 }
                      shape { dim: 16 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
        convolution_param { num_output: 32 kernel_size: 5 stride: 2
                            weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
        lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "fc" type: "InnerProduct" bottom: "norm1" top: "fc"
        inner_product_param { num_output: 10
                              weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc" bottom: "label"
        top: "loss" }
"""

_FLASH_NET = """
name: "flash_dp"
layer { name: "data" type: "Input" top: "x" top: "label"
        input_param { shape { dim: 8 dim: 64 dim: 128 }
                      shape { dim: 8 } } }
layer { name: "attn" type: "Attention" bottom: "x" top: "y"
        attention_param { num_heads: 4 causal: true use_flash: true } }
layer { name: "fc" type: "InnerProduct" bottom: "y" top: "fc"
        inner_product_param { num_output: 10
                              weight_filler { type: "gaussian" std: 0.01 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "fc" bottom: "label"
        top: "loss" }
"""


def train_step_text(net_text: str, precision: str, n_data: int) -> str:
    """Compile the Solver's own one-iteration train step for an abstract
    `data=n_data` v5e mesh: params replicated, feeds batch-sharded —
    what `caffe train -gpu all [-precision bf16]` builds on the chip."""
    from caffe_mpi_tpu.parallel import MeshPlan
    from caffe_mpi_tpu.proto import NetParameter, SolverParameter
    from caffe_mpi_tpu.solver import Solver
    from caffe_mpi_tpu.utils.model_shapes import (input_shapes,
                                                  synthetic_feeds)
    npar = NetParameter.from_text(net_text)
    sp = SolverParameter.from_text(
        'base_lr: 0.01 lr_policy: "fixed" momentum: 0.9 max_iter: 10 '
        f'display: 0 precision: "{precision}"')
    sp.net_param = npar
    solver = Solver(sp)         # concrete state lives on the CPU
    plan = MeshPlan(mesh=tpu_mesh(n_data, 1))
    solver.net.bind_mesh(plan)  # layers specialize on the TPU mesh
    feeds = synthetic_feeds(input_shapes(npar), npar=npar)
    rep = plan.replicated()
    args = [abstract(solver.params, rep), abstract(solver.net_state, rep),
            abstract(solver.opt_state, rep),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=plan.batch_sharded(x.ndim, 0)),
                jax.tree.map(jnp.asarray, feeds)),
            abstract(jnp.int32(0), rep), abstract(solver.base_rng, rep)]
    if solver._guard_on:  # bf16's dynamic loss scale rides the guard carry
        args.append(abstract(solver._guard_state0(), rep))
    try:
        return compile_tpu(solver._iteration_fn(plain=True), *args)
    finally:
        solver.close()


class TestDataParallelStepWithMosaicKernels:
    """The construction that used to fail at lowering with
    "Mosaic kernels cannot be automatically partitioned": a Pallas call
    inside the GSPMD-partitioned train step."""

    def test_bf16_conv_lrn_step_on_four_chips(self):
        text = train_step_text(_CONV_LRN_NET, "bf16", 4)
        assert mosaic_calls(text) >= 2      # LRN forward + backward
        assert "all-reduce" in text         # gradient mean over 'data'

    def test_f32_conv_lrn_step_on_four_chips_is_plain_xla(self):
        # ops/lrn_lax.py is per-sample jnp: GSPMD partitions it by itself
        text = train_step_text(_CONV_LRN_NET, "f32", 4)
        assert mosaic_calls(text) == 0
        assert "all-reduce" in text

    def test_bf16_conv_lrn_step_on_one_chip(self):
        assert mosaic_calls(train_step_text(_CONV_LRN_NET, "bf16", 1)) >= 2

    def test_flash_attention_step_on_four_chips(self):
        text = train_step_text(_FLASH_NET, "f32", 4)
        assert mosaic_calls(text) >= 3
        assert "all-reduce" in text
