"""The latent-attention reference (`reference/joyai_ref.py`) and its counts
against the program, on the CPU: parameters and multiply-accumulates
against the built `Net` at the published widths (built, never initialised),
the configuration against the catalog's row, the reference against the
`Net` at the rehearsal preset, the blocked loss, the controls' planted
faults, the counting functions on cases worked by hand, and the cell's
rehearsal through the command."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import joyai_ref
from test_bench_command import run_cell

CELL = "joyai_flash_bf16_s8k_epshare"
CONFIG = json.loads((BENCH / "configs" / "joyai_llm_flash.json").read_text())
SZ = joyai_ref.sizes_from_config(CONFIG)
TINY = joyai_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY_NET = CONFIG["rehearse"]["solver"].replace("tiny_solver",
                                                "tiny_train_val")


def built(path: str, precision: str = "f32"):
    from caffe_mpi_tpu.net import Net
    from caffe_mpi_tpu.proto import NetParameter
    return Net(NetParameter.from_file(str(ROOT / path)), phase="TRAIN",
               precision=precision)


def feeds_of(tokens) -> dict:
    return {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1),
            "label_mtp": jnp.roll(tokens, -2, axis=1)}


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
    assert by_layer["blk0/attn"] == 26_347_520 \
        == sizes["latent_attention_parameters"]
    assert block("blk0") == 70_391_808 == sizes["dense_block_parameters"]
    assert block("blk1") == block("blk4") == 107_092_224 \
        == sizes["expert_block_parameters"]
    # the MTP module owns neither its table nor its head: the trunk's
    assert "mtp/embed" not in by_layer and "mtp/logits" not in by_layer
    assert block("mtp") == 115_486_976 == sizes["mtp_module_parameters"]
    assert by_layer["embed"] + by_layer["logits"] == 66_191_360 \
        == sizes["embedding_and_head_parameters"]
    total = sum(by_layer.values())
    assert total == 680_441_088 == joyai_ref.param_count(SZ) \
        == sizes["learnable_parameters"]
    macs = joyai_ref.macs_per_sample(SZ, 8192)
    assert macs == net_macs_per_image(net) \
        == sizes["forward_macs_per_sequence_of_8192"]
    assert 566e6 < macs / 8192 < 567e6     # ISSUE: 566 M a token
    # latent attention is 72 % of them, the flash kernels' pairs 44 %
    pairs = joyai_ref.visible_pairs(8192) * 32 * (192 + 128)
    mla = 6 * (8192 * joyai_ref.attention_weights(SZ) + pairs)
    assert 0.71 < mla / macs < 0.73 and 0.43 < 6 * pairs / macs < 0.45


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
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: row[k] for k in CONFIG["reduced"]} == {
        k: CONFIG["published"][k] for k in CONFIG["reduced"]}
    assert CONFIG["vocab_size"] * 8 == row["vocab_size"]
    assert CONFIG["n_routed_experts"] * 16 == row["n_routed_experts"]
    assert CONFIG["num_hidden_layers"] == row["first_k_dense_replace"] + 4


def test_the_committed_recipes_are_what_the_generator_emits():
    import sys
    sys.path.insert(0, str(ROOT / "models"))
    import generate_models as g
    for net, sizes in (("train_val.prototxt", g.JOYAI),
                       ("tiny_train_val.prototxt", g.JOYAI_TINY)):
        text = g.joyai_llm_flash(**sizes, remat=g.JOYAI_REMAT).to_prototxt()
        assert (ROOT / "models" / "joyai_llm_flash" / net).read_text() \
            == text + "\n"


@pytest.mark.parametrize("precision,low,high", [("f32", 0.0, 1e-5),
                                                ("bf16", 1e-3, 3e-2)])
def test_reference_agrees_with_the_net_at_the_rehearsal_preset(
        precision, low, high):
    """f32 to rounding; bf16 off by about its own rounding, which a
    tolerance between the two tells from f32. Both heads."""
    net = built(TINY_NET, precision)
    params, state = net.init(jax.random.PRNGKey(1))
    shape = net.feed_specs["tokens"][0]
    feeds = feeds_of(jax.random.randint(jax.random.PRNGKey(2), shape, 0,
                                        TINY.vocab))
    blobs, _, loss = net.apply(params, state, feeds, train=True,
                               rng=jax.random.PRNGKey(3))
    ref = joyai_ref.from_net(params, TINY)
    wants = joyai_ref.forward(ref, feeds["tokens"], feeds["label"], TINY,
                              q_block=16)
    for blob, want in zip(("logits", "mtp/logits"), wants):
        want = np.asarray(want, np.float64)
        got = np.asarray(blobs[blob].astype(jnp.float32), np.float64)
        assert got.shape == want.shape == (*shape, TINY.vocab)
        assert low <= np.linalg.norm(got - want) / np.linalg.norm(want) < high
    want_loss = float(joyai_ref.loss(ref, feeds["tokens"], feeds["label"],
                                     feeds["label_mtp"], TINY))
    assert abs(float(loss) - want_loss) < max(high, 1e-5) * want_loss


@pytest.fixture(scope="module")
def tiny_case():
    params = joyai_ref.from_net(
        built(TINY_NET).init(jax.random.PRNGKey(4))[0], TINY)
    return params, feeds_of(jax.random.randint(
        jax.random.PRNGKey(5), (2, 32), 0, TINY.vocab))


def test_an_eight_bit_product_stands_well_outside_bf16(tiny_case):
    params, f = tiny_case
    want = np.concatenate([np.asarray(x, np.float64) for x in
                           joyai_ref.forward(params, f["tokens"], f["label"],
                                             TINY)])
    dist = {}
    for name, dt in (("bf16", jnp.bfloat16), ("f8", jnp.float8_e4m3fn)):
        got = np.concatenate([np.asarray(x, np.float64) for x in
                              joyai_ref.forward(params, f["tokens"],
                                                f["label"], TINY,
                                                operand_dtype=dt)])
        dist[name] = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert dist["f8"] > 4 * dist["bf16"] > 0


@pytest.mark.parametrize("vocab_block", [24, 32, 64])
def test_the_blocked_loss_is_the_loss(tiny_case, vocab_block):
    """`loss_blocked` (layers, query blocks, experts and vocabulary blocks
    computed again in the backward pass; a last vocabulary block that is
    part padding) gives `loss` and its gradient, both heads in it."""
    params, f = tiny_case
    args = (f["tokens"], f["label"], f["label_mtp"], TINY)
    want, want_g = jax.value_and_grad(joyai_ref.loss)(params, *args)
    got, got_g = jax.value_and_grad(joyai_ref.loss_blocked)(
        params, *args, 16, vocab_block)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(w))))
    half = joyai_ref.loss_blocked(params, *args, 16, vocab_block,
                                  positions=16)
    cut = lambda a: a[:, :16]
    main, mtp = joyai_ref.forward(params, f["tokens"], f["label"], TINY)
    by_hand = joyai_ref.cross_entropy(cut(main), cut(f["label"])) \
        + 0.3 * joyai_ref.cross_entropy(cut(mtp), cut(f["label_mtp"]))
    assert abs(float(half) - float(by_hand)) < 1e-5


@pytest.fixture(scope="module")
def controls():
    """The driver's `--controls` mode at the rehearsal preset: the
    reference with one fault planted where the program stands, through
    the same two comparisons as the set-up checks."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_mla_lm.py")
    lines = []
    driver.controls(harness.load_cell(CELL, rehearse=True), 7,
                    lambda **fields: lines.append(fields))
    return {(line["control"], line["fault"]): line
            for line in lines if "control" in line}


LOGIT_FAULTS = ["operands_f8_e5m2", "operands_f8_e4m3", "no_rotary",
                "rotary_over_the_whole_head", "bias_weighs_as_well",
                "no_scaling_factor", "no_shared_expert",
                "w_renormalised_over_held"]


@pytest.mark.parametrize("fault", LOGIT_FAULTS)
def test_a_planted_fault_moves_the_logits(controls, fault):
    """At the tiny size the distances are small (one rotation in 8 lanes
    over 32 positions), so the limits that are set on the chip at the
    timed size do not apply; that each fault is seen does."""
    sound = controls["logits", "operands_bf16"]
    assert sound["sound"] and sound["correct"]
    reading = controls["logits", fault]
    assert not reading["sound"] and reading["rel_rms"] > 0
    if fault.startswith("operands"):
        assert reading["rel_rms"] > 4 * sound["rel_rms"]


@pytest.mark.parametrize("fault", LOGIT_FAULTS + ["no_mtp_loss",
                                                  "half_the_positions"])
def test_a_planted_fault_moves_a_leaf_s_gradient(controls, fault):
    """Ten times further than bf16 operands move the worst leaf; whether
    that is over the limit is read on the chip, at the timed size (the
    rehearsal's own limit is wide: a held expert sums four rows there)."""
    sound = controls["grads", "operands_bf16"]
    assert sound["correct"] and sound["worst_leaf_rel"] < 0.05
    reading = controls["grads", fault]
    assert not reading["sound"]
    assert reading["worst_leaf_rel"] > 10 * sound["worst_leaf_rel"]


def test_the_loss_only_faults_are_not_run_on_the_logits(controls):
    assert ("logits", "no_mtp_loss") not in controls
    assert ("logits", "half_the_positions") not in controls


def test_kernel_costs_follow_their_shapes():
    tiles = 64 * 65 // 2
    assert joyai_ref.visible_tiles(8192) == tiles
    assert joyai_ref.visible_pairs(8) == 36
    fwd, nbytes = joyai_ref.flash_cost("flash_fwd", SZ, 1, 8192)
    # QK^T over 192 lanes and PV over 128, 2 FLOPs a multiply-accumulate
    assert fwd == 2 * (192 + 128) * tiles * 128 * 128 * 32
    # q 192 a head, keys 128 a head and 64 once, v and o 128 a head
    assert nbytes == 2 * 8192 * (32 * 192 + 32 * 128 + 64 + 2 * 32 * 128) \
        + 4 * 32 * 8192
    dq, _ = joyai_ref.flash_cost("flash_dq", SZ, 1, 8192)
    dkv, _ = joyai_ref.flash_cost("flash_dkv", SZ, 1, 8192)
    assert dq == 2 * (192 + 128 + 192) * tiles * 128 * 128 * 32
    assert dkv == 2 * (192 + 128 + 128 + 192) * tiles * 128 * 128 * 32
    flops, nbytes = joyai_ref.grouped_cost(4096, SZ)
    assert flops == 2 * 4096 * 3 * 2048 * 768
    assert nbytes == 2 * (2 * 4096 * 2048 + 4 * 4096 * 768
                          + 16 * 3 * 2048 * 768)


def test_the_new_cell_rehearses_through_the_command():
    proc, lines = run_cell(ROOT, "--workload", CELL, "--seed", "3000000001",
                           "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["logits"]["ok"] and checks["logits"]["seq_len"] == 32
    assert checks["logits"]["blobs"] == ["logits", "mtp/logits"]
    # the gradient check ran on every leaf that trains: 70 less 3 routers
    # and 3 selection biases
    assert checks["grads"]["ok"] and checks["grads"]["leaves"] == 64
    assert checks["grads"]["frozen"] == [
        f"{b}/moe/{blob}" for b in ("blk1", "blk2", "mtp")
        for blob in ("gate", "select_bias")]
    assert len(checks["logits"]["routed_rows"]) == 3
    # 4 choices of each of 32 tokens went somewhere; the two held experts
    # got at most all of them
    assert all(0 <= sum(rows) <= 128
               for rows in checks["logits"]["routed_rows"])
    readers = next(line for line in lines
                   if "layer_metric_readers_with_a_value" in line)
    assert "routed_rows_max_over_mean" in \
        readers["layer_metric_readers_with_a_value"]
