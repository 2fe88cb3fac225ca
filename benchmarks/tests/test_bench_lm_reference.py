"""The language-model reference and its counts against the program, on the
CPU: parameters and multiply-accumulates against the built `Net` at the
published widths (built, never initialised), the reference against the
`Net` at the rehearsal preset, the counting functions on cases worked by
hand, and the new cell's rehearsal through the command."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import lm_ref
from test_bench_command import run_cell

CELL = "smallthinker_bf16_s8k_ep4share"
CONFIG = json.loads(
    (BENCH / "configs" / "smallthinker_21b_a3b.json").read_text())
SZ = lm_ref.sizes_from_config(CONFIG)
TINY = lm_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])


def built(path: str, precision: str = "f32"):
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    return Net(NetParameter.from_file(str(ROOT / path)), phase="TRAIN",
               precision=precision)


def test_parameters_and_macs_match_the_program_at_published_widths():
    from caffe_mpi_tpu.utils.flops import net_macs_per_image
    net = built(CONFIG["recipe"]["net"])
    by_layer = {}
    for layer, _, decl in net.learnable_param_decls():
        name = getattr(layer, "name", layer)
        by_layer[name] = by_layer.get(name, 0) + math.prod(decl.shape)
    block = sum(n for name, n in by_layer.items() if name.startswith("blk0/"))
    assert block == 115_512_320 == CONFIG["sizes"]["per_layer_parameters"]
    assert by_layer["embed"] + by_layer["logits"] == 194_478_080
    assert by_layer["ln_f"] == 2_560
    total = sum(by_layer.values())
    assert total == 656_529_920 == lm_ref.param_count(SZ) \
        == CONFIG["sizes"]["learnable_parameters"]
    macs = lm_ref.macs_per_sample(SZ, 8192)
    assert macs == net_macs_per_image(net) \
        == CONFIG["sizes"]["forward_macs_per_sequence_of_8192"]
    assert 312e6 < macs / 8192 < 314e6     # ISSUE: about 313 M a token


def test_the_configuration_keeps_every_published_width():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
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
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert {k: row[k] for k in CONFIG["reduced"]} == {
        k: CONFIG["published"][k] for k in CONFIG["reduced"]}
    assert CONFIG["vocab_size"] * 4 == row["vocab_size"]


@pytest.mark.parametrize("precision,low,high", [("f32", 0.0, 1e-5),
                                                ("bf16", 1e-3, 3e-2)])
def test_reference_agrees_with_the_net_at_the_rehearsal_preset(
        precision, low, high):
    """f32 to rounding; bf16 off by about its own rounding, which a
    tolerance between the two tells from f32."""
    from caffe_mpi_tpu.proto import SolverParameter
    sp = SolverParameter.from_file(str(ROOT / CONFIG["rehearse"]["solver"]))
    net = built(sp.net, precision)
    params, state = net.init(jax.random.PRNGKey(1))
    shape = net.feed_specs["tokens"][0]
    tokens = jax.random.randint(jax.random.PRNGKey(2), shape, 0, TINY.vocab)
    feeds = {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1)}
    blobs, _, loss = net.apply(params, state, feeds, train=True,
                               rng=jax.random.PRNGKey(3))
    ref = lm_ref.from_net(params, TINY)
    want = np.asarray(lm_ref.forward(ref, tokens, TINY, q_block=16),
                      np.float64)
    got = np.asarray(blobs["logits"].astype(jnp.float32), np.float64)
    assert got.shape == want.shape == (*shape, TINY.vocab)
    assert low <= np.linalg.norm(got - want) / np.linalg.norm(want) < high
    want_loss = float(lm_ref.loss(ref, tokens, feeds["label"], TINY))
    assert abs(float(loss) - want_loss) < max(high, 1e-5) * want_loss


def test_an_eight_bit_product_stands_well_outside_bf16():
    """The reading 'one precision below the configuration's': the same
    reference with every product's operands rounded to an 8-bit float
    stands several times further from float32 than bfloat16 does."""
    params = lm_ref.from_net(
        built(CONFIG["rehearse"]["solver"].replace(
            "tiny_solver", "tiny_train_val")).init(
                jax.random.PRNGKey(1))[0], TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                TINY.vocab)
    want = np.asarray(lm_ref.forward(params, tokens, TINY), np.float64)
    dist = {}
    for name, dt in (("bf16", jnp.bfloat16), ("f8", jnp.float8_e4m3fn)):
        got = np.asarray(lm_ref.forward(params, tokens, TINY,
                                        operand_dtype=dt), np.float64)
        dist[name] = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert dist["f8"] > 4 * dist["bf16"] > 0


@pytest.mark.parametrize("vocab_block", [24, 32, 64])
def test_the_blocked_loss_is_the_loss(vocab_block):
    """`loss_blocked` (layers, query blocks, experts and vocabulary blocks
    computed again in the backward pass; a last vocabulary block that is
    part padding) gives `loss` and its gradient."""
    params = lm_ref.from_net(
        built(CONFIG["rehearse"]["solver"].replace(
            "tiny_solver", "tiny_train_val")).init(
                jax.random.PRNGKey(4))[0], TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0,
                                TINY.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    want, want_g = lm_ref.loss_and_grads(params, tokens, labels, TINY)
    got, got_g = jax.value_and_grad(lm_ref.loss_blocked)(
        params, tokens, labels, TINY, 16, vocab_block)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(w))))
    half = lm_ref.loss_blocked(params, tokens, labels, TINY, 16, vocab_block,
                               positions=16)
    assert abs(float(half) - float(lm_ref.loss(
        params, tokens[:, :16], labels[:, :16], TINY))) < 1e-5


@pytest.fixture(scope="module")
def controls():
    """The driver's `--controls` mode at the rehearsal preset: the
    reference with one fault planted where the program stands, through
    the same two comparisons as the set-up checks."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_lm.py")
    lines = []
    driver.controls(harness.load_cell(CELL, rehearse=True), 7,
                    lambda **fields: lines.append(fields))
    return {(line["control"], line["fault"]): line
            for line in lines if "control" in line}


@pytest.mark.parametrize("fault", [
    "operands_f8_e5m2", "operands_f8_e4m3", "no_window", "no_rotary",
    "w_renormalised_over_held"])
def test_a_planted_fault_moves_the_logits_further_than_bf16(controls, fault):
    """At the tiny size the distances are small (a window of 8 in 32, one
    rotation in 16 dimensions), so the limits that are set on the chip at
    the timed size do not apply; the order does."""
    sound = controls["logits", "operands_bf16"]
    assert sound["sound"] and sound["correct"]
    reading = controls["logits", fault]
    assert not reading["sound"] and reading["rel_rms"] > 0
    if fault.startswith("operands"):
        assert reading["rel_rms"] > 4 * sound["rel_rms"]


@pytest.mark.parametrize("fault", [
    "operands_f8_e5m2", "operands_f8_e4m3", "no_window",
    "w_renormalised_over_held", "half_the_positions"])
def test_a_planted_fault_fails_the_gradient_comparison(controls, fault):
    sound = controls["grads", "operands_bf16"]
    assert sound["correct"] and sound["worst_leaf_rel"] < 0.05
    reading = controls["grads", fault]
    assert not reading["correct"]
    assert reading["worst_leaf_rel"] > 10 * sound["worst_leaf_rel"]


def test_visible_pairs_and_tiles_by_hand():
    # causal, no window: the lower triangle
    assert lm_ref.visible_pairs(8, 0) == 36
    # window 3 over 8: rows see 1, 2, 3, 3, 3, 3, 3, 3 keys
    assert lm_ref.visible_pairs(8, 3) == 21
    brute = lambda s, w: int(np.sum(np.asarray(lm_ref.visible(
        jnp.arange(s), jnp.arange(s), w))))
    for s, w in ((32, 8), (32, 0), (300, 130), (8192, 4096)):
        assert lm_ref.visible_pairs(s, w) == brute(s, w)
    # 8192 in tiles of 128: 64 x 65 / 2 causal tiles; a window of 4096
    # leaves each query tile its own and the 32 before it
    assert lm_ref.visible_tiles(8192, 0) == 64 * 65 // 2
    assert lm_ref.visible_tiles(8192, 4096) == sum(
        min(q, 32) + 1 for q in range(64))
    mask = np.asarray(lm_ref.visible(jnp.arange(512), jnp.arange(512), 200))
    tiles = sum(mask[a:a + 128, b:b + 128].any()
                for a in range(0, 512, 128) for b in range(0, 512, 128))
    assert lm_ref.visible_tiles(512, 200) == tiles
    # the window removes a quarter of the causal pairs at S = 8192
    share = lm_ref.visible_pairs(8192, 4096) / lm_ref.visible_pairs(8192, 0)
    assert 0.74 < share < 0.76


def test_kernel_costs_follow_their_shapes():
    flops, nbytes = lm_ref.flash_cost("flash_fwd", SZ, 0, 1, 8192)
    tiles = 64 * 65 // 2
    assert flops == 4 * 128 * tiles * 128 * 128 * 28
    assert nbytes == 2 * (2 * 28 + 2 * 4) * 8192 * 128 + 4 * 28 * 8192
    windowed, _ = lm_ref.flash_cost("flash_fwd", SZ, 1, 1, 8192)
    assert 0.74 < windowed / flops < 0.78
    dq, _ = lm_ref.flash_cost("flash_dq", SZ, 0, 1, 8192)
    dkv, _ = lm_ref.flash_cost("flash_dkv", SZ, 0, 1, 8192)
    assert (dq, dkv) == (flops * 6 // 4, flops * 2)
    flops, nbytes = lm_ref.grouped_cost(12288, SZ)
    assert flops == 2 * 12288 * 3 * 2560 * 768
    assert nbytes == 2 * (2 * 12288 * 2560 + 4 * 12288 * 768
                          + 16 * 3 * 2560 * 768)


def test_the_new_cell_rehearses_through_the_command():
    proc, lines = run_cell(ROOT, "--workload", CELL, "--seed", "3000000001",
                           "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["logits"]["ok"] and checks["logits"]["seq_len"] == 32
    # the gradient check ran on every leaf that trains: 35 less 4 routers
    assert checks["grads"]["ok"] and checks["grads"]["leaves"] == 31
    assert checks["grads"]["frozen"] == [f"blk{l}/moe/gate"
                                         for l in range(4)]
    assert len(checks["logits"]["moe_rows"]) == 4
    # every (token, choice) pair of the 32 tokens went somewhere; the two
    # held experts got at most all of them
    assert all(0 <= sum(rows) <= 64 for rows in checks["logits"]["moe_rows"])
    readers = next(line for line in lines
                   if "layer_metric_readers_with_a_value" in line)
    assert "moe_rows_max_over_mean" in \
        readers["layer_metric_readers_with_a_value"]
