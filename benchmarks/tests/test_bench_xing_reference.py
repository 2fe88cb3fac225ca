"""The hyper-connection reference (`reference/xing_ref.py`) and its counts
against the program, on the CPU: parameters and multiply-accumulates
against the built `Net` at the published widths (built, never initialised),
the configuration against the catalog's row, the committed recipes against
the generator, the reference against the `Net` at the rehearsal preset,
the blocked loss, the counting functions on cases worked by hand, the
cell's three readers on hand-made records, and the cell's rehearsal
through the command."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import joyai_ref, xing_ref
from test_bench_command import run_cell

CELL = "xing4_bf16_s4k_mhc4_ep8share"
CONFIG = json.loads((BENCH / "configs" / "xing4_29b_a4b.json").read_text())
SZ = xing_ref.sizes_from_config(CONFIG)
TINY = xing_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY_NET = CONFIG["rehearse"]["solver"].replace("tiny_solver",
                                                "tiny_train_val")
READERS = ("mhc_ms_per_step", "mhc_sinkhorn_ms_per_step", "mhc_roofline")


def built(path: str, precision: str = "f32"):
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    return Net(NetParameter.from_file(str(ROOT / path)), phase="TRAIN",
               precision=precision)


def feeds_of(tokens) -> dict:
    return {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1)}


def test_parameters_and_macs_match_the_program_at_published_widths():
    from caffe_mpi_tpu.utils.flops import net_macs_per_image
    net = built(CONFIG["recipe"]["net"])
    by_layer = {}
    for layer, _, decl in net.learnable_param_decls():
        name = getattr(layer, "name", layer)
        by_layer[name] = by_layer.get(name, 0) + math.prod(decl.shape)
    block = lambda b: sum(n for name, n in by_layer.items()
                          if name.startswith(b + "/"))
    sizes = CONFIG["sizes"]
    assert by_layer["blk0/attn"] == 28_411_136 \
        == sizes["latent_attention_parameters"]
    # phi (24, 4 x 3,584), bias (24), alpha (3)
    assert by_layer["blk0/hc1"] == 24 * 14_336 + 27 == xing_ref.hc_weights(
        SZ) == sizes["hyper_connection_parameters"]
    assert block("blk0") == 128_196_918 == sizes["dense_block_parameters"]
    assert block("blk1") == block("blk4") == 128_426_358 \
        == sizes["expert_block_parameters"]
    assert by_layer["embed"] + by_layer["logits"] == 117_440_512 \
        == sizes["embedding_and_head_parameters"]
    total = sum(by_layer.values())
    assert total == 759_346_446 == xing_ref.param_count(SZ) \
        == sizes["learnable_parameters"]
    macs = xing_ref.macs_per_sample(SZ, 4096)
    assert macs == net_macs_per_image(net) \
        == sizes["forward_macs_per_sequence_of_4096"]
    assert 475e6 < macs / 4096 < 477e6
    # ten hyper-connections: 0.9 % of the products
    hc = 10 * 4096 * xing_ref.hc_macs_per_token(SZ)
    assert 0.008 < hc / macs < 0.01


def test_the_configuration_keeps_every_published_width():
    row = None
    # a JSON-lines catalog of published configurations, one
    # {"source_url": ..., "config": {...}} object a line
    catalog = os.environ.get("ARCHITECTURE_CATALOG", "")
    try:
        lines = open(catalog).read().splitlines()
    except OSError:
        pytest.skip("the catalog of architectures is not on this machine")
    for line in lines:
        entry = json.loads(line)
        if entry["source_url"] == CONFIG["source"]:
            row = entry["config"]
    assert row is not None
    differs = {k for k, v in row.items() if CONFIG.get(k, object()) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert {k: row[k] for k in CONFIG["reduced"]} == {
        k: CONFIG["published"][k] for k in CONFIG["reduced"]}
    assert CONFIG["vocab_size"] * 8 == row["vocab_size"]
    assert CONFIG["n_routed_experts"] * 8 == row["n_routed_experts"]
    assert CONFIG["num_hidden_layers"] == CONFIG["dense_layers_held"] + 4


def test_the_committed_recipes_are_what_the_generator_emits():
    import sys
    sys.path.insert(0, str(ROOT / "models"))
    import generate_models as g
    for net, sizes in (("train_val.prototxt", g.XING),
                       ("tiny_train_val.prototxt", g.XING_TINY)):
        text = g.xing4_29b_a4b(**sizes, remat=g.XING_REMAT).to_prototxt()
        assert (ROOT / "models" / "xing4_29b_a4b" / net).read_text() \
            == text + "\n"


@pytest.mark.parametrize("precision,low,high", [("f32", 0.0, 1e-5),
                                                ("bf16", 1e-3, 3e-2)])
def test_reference_agrees_with_the_net_at_the_rehearsal_preset(
        precision, low, high):
    """f32 to rounding; bf16 off by about its own rounding, which a
    tolerance between the two tells from f32."""
    net = built(TINY_NET, precision)
    params, state = net.init(jax.random.PRNGKey(1))
    shape = net.feed_specs["tokens"][0]
    feeds = feeds_of(jax.random.randint(jax.random.PRNGKey(2), shape, 0,
                                        TINY.vocab))
    blobs, _, loss = net.apply(params, state, feeds, train=True,
                               rng=jax.random.PRNGKey(3))
    ref = xing_ref.from_net(params, TINY)
    want = np.asarray(xing_ref.forward(ref, feeds["tokens"], TINY,
                                       q_block=16), np.float64)
    got = np.asarray(blobs["logits"].astype(jnp.float32), np.float64)
    assert got.shape == want.shape == (*shape, TINY.vocab)
    assert low <= np.linalg.norm(got - want) / np.linalg.norm(want) < high
    want_loss = float(xing_ref.loss(ref, feeds["tokens"], feeds["label"],
                                    TINY))
    assert abs(float(loss) - want_loss) < max(high, 1e-5) * want_loss


@pytest.fixture(scope="module")
def tiny_case():
    params = xing_ref.from_net(
        built(TINY_NET).init(jax.random.PRNGKey(4))[0], TINY)
    return params, feeds_of(jax.random.randint(
        jax.random.PRNGKey(5), (2, 32), 0, TINY.vocab))


@pytest.mark.parametrize("vocab_block", [24, 64])
def test_the_blocked_loss_is_the_loss(tiny_case, vocab_block):
    """`loss_blocked` (layers, query blocks, experts and vocabulary blocks
    computed again in the backward pass; a last vocabulary block that is
    part padding) gives `loss` and its gradient."""
    params, f = tiny_case
    args = (f["tokens"], f["label"], TINY)
    want, want_g = jax.value_and_grad(xing_ref.loss)(params, *args)
    got, got_g = jax.value_and_grad(xing_ref.loss_blocked)(
        params, *args, 16, vocab_block)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    for layer, w in zip(got_g["layers"], want_g["layers"]):
        for name in w:
            if name in ("hc1", "hc2"):
                # sums of terms that nearly cancel (drivers/train_mhc_lm.py
                # `held_together`): the two losses' summation orders move
                # an element by 1e-2 of itself; the layer's three blobs
                # are held together, by their norm
                flat = lambda t: np.concatenate([
                    np.asarray(t[name][b], np.float64).ravel()
                    for b in ("phi", "bias", "alpha")])
                g, ref = flat(layer), flat(w)
                assert np.linalg.norm(g - ref) < 1e-4 * np.linalg.norm(ref)
                continue
            np.testing.assert_allclose(
                layer[name], w[name], rtol=1e-4,
                atol=1e-6 * float(jnp.max(jnp.abs(w[name]))))
    for name in ("embed", "g_f", "head"):
        np.testing.assert_allclose(
            got_g[name], want_g[name], rtol=1e-4,
            atol=1e-6 * float(jnp.max(jnp.abs(want_g[name]))))


def test_kernel_and_stream_costs_follow_their_shapes():
    tiles = 32 * 33 // 2
    fwd, nbytes = xing_ref.flash_cost("flash_fwd", SZ, 1, 4096)
    # QK^T over 192 lanes and PV over 128, 2 FLOPs a multiply-accumulate
    assert fwd == 2 * (192 + 128) * tiles * 128 * 128 * 32
    assert nbytes == 2 * 4096 * (32 * 192 + 32 * 128 + 64 + 2 * 32 * 128) \
        + 4 * 32 * 4096
    assert xing_ref.flash_cost is joyai_ref.flash_cost
    # a sub-layer's stream, bf16: 4,096 x 4 x 3,584 x 2 bytes; forward 3
    # of them, backward 6, the channels' 5 and the coefficients' 4
    stream, channels = 2 * 4096 * 4 * 3584, 2 * 4096 * 3584
    coef = 4 * 4096 * 20
    assert xing_ref.mhc_cost(SZ, 1, 4096) \
        == 9 * stream + 5 * channels + 4 * coef \
        == CONFIG["sizes"]["mhc_bytes_a_sub_layer_forward_and_backward"]
    # its least time, ten sub-layers at the v5e's HBM peak: 14.7 ms
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    least = 10 * xing_ref.mhc_cost(SZ, 1, 4096) \
        / peaks["hbm_bytes_per_s"]["value"]
    assert 0.014 < least < 0.0155
    # a hyper-connection's products, a token: 24 outputs of 4 x 3,584
    # lanes, and the mixes' 4 + 16 + 4 multiply-adds a channel
    assert xing_ref.hc_macs_per_token(SZ) == 24 * 14_336 + 24 * 3584


def read(metric: str, run: dict, trace):
    import run as harness
    return harness.load_module(
        BENCH / "layer_metrics" / f"{metric}.py").compute(run, trace)


def test_the_cell_s_readers_are_these_three():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(READERS)
    assert {m["moves"] for m in mine} == {"train_samples_per_s"}
    assert {m["layer"] for m in mine} == {"Net_layers"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


RECORD = {"cell": CELL, "traced_iters": 0, "peaks": {},
          "samples_per_iter": 1, "seq_len": 4096,
          "xing_sizes": xing_ref.sizes_record(SZ)}


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_without_a_trace_returns_none_and_never_raises(metric):
    """On this cell's record and on another program's (the parent's: no
    such scopes, no such sizes)."""
    for run_record in (RECORD, {"cell": CELL},
                       {"cell": CELL, "traced_iters": 10}):
        assert read(metric, run_record, None) is None, metric


def test_the_readers_on_a_hand_made_reduction(monkeypatch):
    """With the layer table and the scope's seconds handed in: the two
    layer types' rows summed a step, the Sinkhorn scope's seconds a step,
    and the roofline ten sub-layers' least time over the first."""
    import scope_reduce
    import span_reduce
    peaks = {k: v["value"] for k, v in json.loads(
        (BENCH / "peaks.json").read_text())["TPU v5 lite"].items()}
    run = {**RECORD, "traced_iters": 10, "peaks": peaks}
    rows = [{"type": "HyperConnectionPre", "seconds": 0.2},
            {"type": "HyperConnectionPost", "seconds": 0.1},
            {"type": "Attention", "seconds": 5.0}]
    monkeypatch.setattr(span_reduce, "for_run",
                        lambda run, trace: {"layers": rows})
    monkeypatch.setattr(scope_reduce, "for_run",
                        lambda run, trace, scope: 0.05
                        if scope == "mhc.sinkhorn" else None)
    assert abs(read("mhc_ms_per_step", run, {}) - 30.0) < 1e-9
    assert abs(read("mhc_sinkhorn_ms_per_step", run, {}) - 5.0) < 1e-9
    least_ms = 1e3 * 10 * xing_ref.mhc_cost(SZ, 1, 4096) \
        / peaks["hbm_bytes_per_s"]
    assert abs(read("mhc_roofline", run, {}) - 100 * least_ms / 30.0) \
        < 1e-9
    # the parent's record: no sizes, no share
    assert read("mhc_roofline", {k: v for k, v in run.items()
                                 if k != "xing_sizes"}, {}) is None


@pytest.fixture(scope="module")
def controls():
    """The driver's `--controls` mode at the rehearsal preset: the
    reference with one fault planted where the program stands, through
    the same two comparisons as the set-up checks."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_mhc_lm.py")
    lines = []
    driver.controls(harness.load_cell(CELL, rehearse=True), 7,
                    lambda **fields: lines.append(fields))
    return {(line["control"], line["fault"]): line
            for line in lines if "control" in line}


FAULTS = ["operands_f8_e4m3", *xing_ref.FAULTS]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_logits_and_a_leaf(controls, fault):
    """At the tiny size the distances are small, so the limits that are set
    on the chip at the timed size do not apply; that each fault is seen,
    by the logits and by the gradient, does. Every fault's gradient is
    read, the 8-bit product's too, so that each gradient limit has a
    reading above it; the sound control's is not."""
    sound = controls["logits", "operands_bf16"]
    assert sound["sound"] and sound["correct"]
    assert ("grads", "operands_bf16") not in controls
    logits = controls["logits", fault]
    assert not logits["sound"] and logits["rel_rms"] > 0
    grads = controls["grads", fault]
    assert not grads["sound"] and grads["worst_leaf_rel"] > 0
    assert grads["whole_rel"] > 0


def test_the_new_cell_rehearses_through_the_command():
    proc, lines = run_cell(ROOT, "--workload", CELL, "--seed", "3000000001",
                           "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["logits"]["ok"] and checks["logits"]["seq_len"] == 32
    assert checks["logits"]["blob"] == "logits"
    # the gradient check ran on every leaf that trains: 67 less 2 routers
    # and 2 selection biases, each hyper-connection's three as one
    assert checks["grads"]["ok"] and checks["grads"]["leaves"] == 63 - 2 * 6
    assert checks["grads"]["frozen"] == [
        f"{b}/moe/{blob}" for b in ("blk1", "blk2")
        for blob in ("gate", "select_bias")]
    # only the expert layers count rows
    assert len(checks["logits"]["routed_rows"]) == 2
    assert all(0 <= sum(rows) <= 128
               for rows in checks["logits"]["routed_rows"])
    assert checks["loss"]["ok"]


def test_every_latent_driver_function_the_cell_runs_reads_xing_reference():
    """`drivers/train_mhc_lm.py` runs `train_mla_lm`'s functions over a
    globals dict of its own (`_ours`). A function reached from the cell's
    entry points that still resolves `joyai_ref` in `train_mla_lm`'s own
    globals would check this cell against joyai's reference: every
    benchmark function reached by name from `run` and `controls` that
    reads `joyai_ref` has to find `xing_ref` there."""
    import types

    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_mhc_lm.py")

    def names_of(code):
        names, codes = set(), [code]
        while codes:
            c = codes.pop()
            names |= set(c.co_names)
            codes += [k for k in c.co_consts if isinstance(k, types.CodeType)]
        return names

    ours = lambda f: (isinstance(f, types.FunctionType)
                      and f.__code__.co_filename.startswith(str(BENCH)))
    seen, readers, stack = set(), [], [driver.run, driver.controls]
    while stack:
        fn = stack.pop()
        if fn in seen:
            continue
        seen.add(fn)
        names = names_of(fn.__code__)
        if "joyai_ref" in names:
            readers.append(fn)
            assert fn.__globals__["joyai_ref"] is xing_ref, fn.__qualname__
        cells = [c.cell_contents for c in fn.__closure__ or ()
                 if c.cell_contents is not None]
        for value in [fn.__globals__.get(n) for n in names] + cells:
            if ours(value):
                stack.append(value)
            elif isinstance(value, (types.ModuleType,
                                    types.SimpleNamespace)):
                stack += [getattr(value, n) for n in names
                          if ours(getattr(value, n, None))]
    # the walk reached the rebound copies of train_mla_lm's functions, and
    # no function of that file over its own globals
    assert "run" in {f.__name__ for f in readers}
    mla = [f for f in seen if f.__code__.co_filename.endswith(
        "train_mla_lm.py")]
    assert {f.__name__ for f in mla} >= {"run", "grads_check", "fresh_net"}
    assert all(f.__globals__ is driver._scope for f in mla), mla
