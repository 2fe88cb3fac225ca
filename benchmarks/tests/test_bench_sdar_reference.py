"""The block-diffusion reference (`reference/sdar_ref.py`) and its counts
against the program, on the CPU: parameters and multiply-accumulates
against the built `Net` at the published widths (built, never initialised),
the configuration against the catalog's row, the reference against the
`Net` at the rehearsal preset, the blocked loss, the mask's definition and
its planted faults, the controls, the counting functions on cases worked by
hand, the six readers on a record without a trace, and the cell's rehearsal
through the command."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import sdar_ref
from test_bench_command import run_cell

CELL = "sdar_bf16_s8k_bd4_ep8share"
CONFIG = json.loads((BENCH / "configs" / "sdar_30b_a3b.json").read_text())
SZ = sdar_ref.sizes_from_config(CONFIG)
TINY = sdar_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY_NET = CONFIG["rehearse"]["solver"].replace("tiny_solver",
                                                "tiny_train_val")


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
    block = lambda b: sum(n for name, n in by_layer.items()
                          if name.startswith(b + "/"))
    sizes = CONFIG["sizes"]
    # q, k, v and o products and the two per-head norm scales
    assert by_layer["blk0/attn"] == 2048 * (4096 + 2 * 512) \
        + 4096 * 2048 + 2 * 128
    assert block("blk0") == block("blk4") == 94_638_336 \
        == sizes["per_layer_parameters"]
    assert by_layer["embed"] + by_layer["logits"] + by_layer["ln_f"] \
        == 77_793_280 == sizes["embedding_and_head_parameters"]
    total = sum(by_layer.values())
    assert total == 550_984_960 == sdar_ref.param_count(SZ) \
        == sizes["learnable_parameters"]
    macs = sdar_ref.macs_per_sample(SZ, 8192)
    assert macs == net_macs_per_image(net) \
        == sizes["forward_macs_per_sequence_of_8192"]
    # the products over the visible pairs are 55 % of the forward
    # multiply-accumulates at 5 layers, the head over the noisy half 6 %
    pairs = 2 * sdar_ref.visible_pairs(8192, 4) * 4096 * SZ.layers
    assert 0.54 < pairs / macs < 0.56
    assert 0.06 < 8192 * 2048 * 18992 / macs < 0.07


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
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: row[k] for k in CONFIG["reduced"]} == {
        k: CONFIG["published"][k] for k in CONFIG["reduced"]}
    assert CONFIG["vocab_size"] * 8 == row["vocab_size"]
    assert CONFIG["num_experts"] * 8 == row["num_experts"]
    assert 4 <= CONFIG["num_hidden_layers"] <= 6
    # the mask id is the slice's last id
    assert CONFIG["block_diffusion"]["mask_id"] == CONFIG["vocab_size"] - 1


def test_the_committed_recipes_are_what_the_generator_emits():
    import sys
    sys.path.insert(0, str(ROOT / "models"))
    import generate_models as g
    for net, sizes in (("train_val.prototxt", g.SDAR),
                       ("tiny_train_val.prototxt", g.SDAR_TINY)):
        text = g.sdar(**sizes, remat=g.SDAR_REMAT).to_prototxt()
        assert (ROOT / "models" / "sdar_30b_a3b" / net).read_text() \
            == text + "\n"
    assert g.SDAR["layers"] == CONFIG["num_hidden_layers"]
    assert g.SDAR["vocab"] == CONFIG["vocab_size"]


def drawn(net, params, state, x0, rng):
    blobs, _, loss = net.apply(params, state, {"tokens": x0}, train=True,
                               rng=rng)
    return blobs, loss


@pytest.mark.parametrize("precision,low,high", [("f32", 0.0, 1e-5),
                                                ("bf16", 1e-3, 3e-2)])
def test_reference_agrees_with_the_net_at_the_rehearsal_preset(
        precision, low, high):
    """f32 to rounding; bf16 off by about its own rounding, which a
    tolerance between the two tells from f32."""
    net = built(TINY_NET, precision)
    params, state = net.init(jax.random.PRNGKey(1))
    shape = net.feed_specs["tokens"][0]
    x0 = jax.random.randint(jax.random.PRNGKey(2), shape, 0, TINY.mask_id)
    blobs, loss = drawn(net, params, state, x0, jax.random.PRNGKey(3))
    ref = sdar_ref.from_net(params, TINY)
    want = np.asarray(sdar_ref.forward(ref, blobs["ids"], TINY, q_block=16),
                      np.float64)
    got = np.asarray(blobs["logits"].astype(jnp.float32), np.float64)
    assert got.shape == want.shape == (*shape, TINY.vocab)
    assert low <= np.linalg.norm(got - want) / np.linalg.norm(want) < high
    want_loss = float(sdar_ref.loss(ref, blobs["ids"], blobs["label"],
                                    blobs["weight"], TINY))
    assert abs(float(loss) - want_loss) < max(high, 1e-5) * want_loss
    faults = sdar_ref.noise_faults(x0, blobs["ids"], blobs["label"],
                                   blobs["weight"], TINY)
    assert not any(v for k, v in faults.items() if k != "masked_share")


@pytest.fixture(scope="module")
def tiny_case():
    net = built(TINY_NET)
    params, state = net.init(jax.random.PRNGKey(4))
    x0 = jax.random.randint(jax.random.PRNGKey(5), (1, 32), 0, TINY.mask_id)
    blobs, _ = drawn(net, params, state, x0, jax.random.PRNGKey(6))
    return (sdar_ref.from_net(params, TINY), x0,
            (blobs["ids"], blobs["label"], blobs["weight"]))


def test_the_noise_check_sees_each_fault(tiny_case):
    _, x0, (ids, labels, weights) = tiny_case
    masked = labels != TINY.ignore_label
    at = int(jnp.argmax(masked[0]))          # a masked position
    free = int(jnp.argmin(masked[0]))        # one that is not
    read = lambda **k: sdar_ref.noise_faults(
        k.get("x0", x0), k.get("ids", ids), k.get("labels", labels),
        k.get("weights", weights), TINY)
    sound = read()
    assert not any(v for k, v in sound.items() if k != "masked_share")
    assert sound["masked_share"] == float(jnp.mean(masked))
    assert read(ids=ids.at[0, 32 + free].add(1))["clean_half_is_not_x0"] == 1
    assert read(ids=ids.at[0, at].set(x0[0, at]))["noisy_half_wrong"] == 1
    assert read(ids=ids.at[0, free].set(TINY.mask_id))[
        "noisy_half_wrong"] == 1
    assert read(labels=labels.at[0, at].add(1))["label_is_not_x0"] == 1
    assert read(weights=weights.at[0, at].mul(2.0))[
        "weight_not_one_a_block"] >= 1
    assert read(weights=weights * 0.5)["weight_out_of_range"] >= 1
    assert read(weights=weights.at[0, free].set(1.0))[
        "weight_where_not_masked"] == 1
    assert read(x0=x0.at[0, free].set(TINY.mask_id))[
        "clean_token_is_mask_id"] == 1


def test_an_eight_bit_product_stands_well_outside_bf16(tiny_case):
    params, _, (ids, _, _) = tiny_case
    want = np.asarray(sdar_ref.forward(params, ids, TINY), np.float64)
    dist = {}
    for name, dt in (("bf16", jnp.bfloat16), ("f8", jnp.float8_e4m3fn)):
        got = np.asarray(sdar_ref.forward(params, ids, TINY,
                                          operand_dtype=dt), np.float64)
        dist[name] = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert dist["f8"] > 4 * dist["bf16"] > 0


@pytest.mark.parametrize("vocab_block", [24, 32, 64])
def test_the_blocked_loss_is_the_loss(tiny_case, vocab_block):
    """`loss_blocked` (layers, query blocks, experts and vocabulary blocks
    computed again in the backward pass; a last vocabulary block that is
    part padding) gives `loss` and its gradient."""
    params, _, draw = tiny_case
    want, want_g = jax.value_and_grad(sdar_ref.loss)(params, *draw, TINY)
    got, got_g = jax.value_and_grad(sdar_ref.loss_blocked)(
        params, *draw, TINY, 16, vocab_block)
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(w))))


def test_the_loss_s_planted_faults_by_hand(tiny_case):
    params, _, (ids, labels, weights) = tiny_case
    logp = np.asarray(jax.nn.log_softmax(
        sdar_ref.forward(params, ids, TINY), axis=-1), np.float64)[0]
    x0, lab, w = (np.asarray(a)[0] for a in (ids[:, 32:], labels, weights))
    masked = lab != TINY.ignore_label
    ce = -logp[np.arange(32), x0]
    by_hand = {"weighted_masked": np.sum(w * ce * masked) / 32,
               "unweighted": np.sum(ce * masked) / 32,
               "all_positions": np.sum(np.where(masked, w, 1.0) * ce) / 32}
    for how, want in by_hand.items():
        got = float(sdar_ref.loss_blocked(params, ids, labels, weights, TINY,
                                          16, 32, loss=how))
        assert abs(got - want) < 1e-5 * want, how


@pytest.mark.parametrize("half,block", [(8, 2), (12, 4), (9, 4), (16, 1)])
def test_the_mask_is_the_four_cases(half, block):
    at = jnp.arange(2 * half)
    seen = np.asarray(sdar_ref.visible(at, at, half, block))
    blk = lambda i: (i % half) // block
    pairs = 0
    for i in range(2 * half):
        for j in range(2 * half):
            want = (blk(i) == blk(j)) if i < half and j < half else \
                (blk(j) < blk(i)) if i < half <= j else \
                (blk(j) <= blk(i)) if i >= half and j >= half else False
            assert seen[i, j] == want, (i, j)
            pairs += want
    assert pairs == sdar_ref.visible_pairs(half, block)
    # each planted mask differs from the sound one
    for mask in sdar_ref.MASKS[1:]:
        if block > 1 or mask in ("causal", "noisy_sees_own_clean"):
            assert (np.asarray(sdar_ref.visible(at, at, half, block, mask))
                    != seen).any(), mask


@pytest.mark.parametrize("half,block", [(256, 4), (320, 4), (200, 32),
                                        (192, 64)])
def test_visible_tiles_count_the_definition(half, block):
    s = 2 * half
    at = jnp.arange(s)
    seen = np.asarray(sdar_ref.visible(at, at, half, block))
    n = -(-s // 128)
    padded = np.zeros((n * 128, n * 128), bool)
    padded[:s, :s] = seen
    tiles = padded.reshape(n, 128, n, 128).any((1, 3))
    assert sdar_ref.visible_tiles(half, block) == int(tiles.sum())


@pytest.fixture(scope="module")
def controls():
    """The driver's `--controls` mode at the rehearsal preset: the
    reference with one fault planted where the program stands, through
    the same comparisons as the set-up checks."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_bd_lm.py")
    lines = []
    driver.controls(harness.load_cell(CELL, rehearse=True), 7,
                    lambda **fields: lines.append(fields))
    return {(line["control"], line["fault"]): line
            for line in lines if "control" in line}


MASK_FAULTS = [f"mask_{m}" for m in sdar_ref.MASKS[1:]]
LOGIT_FAULTS = ["operands_f8_e4m3", "positions_0_to_2L", "no_qk_norm"] \
    + MASK_FAULTS


@pytest.mark.parametrize("fault", MASK_FAULTS)
def test_the_probe_catches_every_planted_mask(controls, fault):
    """What a row sees inside its block hardly moves the net's logits;
    the probe's inputs make the mask decide the result, so each wrong mask
    stands far outside the limit that rounding stays far inside."""
    reading = controls["mask_probe", fault]
    assert not reading["sound"] and not reading["correct"]
    assert reading["worst_rel_rms"] > 4 * reading["rel_rms_max"]


@pytest.mark.parametrize("fault", LOGIT_FAULTS)
def test_a_planted_fault_moves_the_logits(controls, fault):
    """At the tiny size the distances are small, so the limits that are
    set on the chip at the timed size do not apply; that each fault is seen
    does."""
    sound = controls["logits", "operands_bf16"]
    assert sound["sound"] and sound["correct"]
    reading = controls["logits", fault]
    assert not reading["sound"] and reading["rel_rms"] > 0
    if fault.startswith("operands"):
        assert reading["rel_rms"] > 4 * sound["rel_rms"]


@pytest.mark.parametrize("fault", ["weights_dropped",
                                   "loss_over_all_positions"])
def test_the_loss_s_value_catches_what_defines_the_loss(controls, fault):
    """The gradient limits stand above the mode in which the masked rows
    change expert together; the loss's own value holds its definition."""
    sound = controls["grads", "operands_bf16"]
    assert sound["loss_rel"] < 1e-3
    reading = controls["grads", fault]
    assert not reading["correct"]
    assert reading["loss_rel"] > 0.3 > 5 * reading["loss_rel_max"]


@pytest.mark.parametrize("fault", LOGIT_FAULTS + ["weights_dropped",
                                                  "loss_over_all_positions"])
def test_a_planted_fault_moves_a_leaf_s_gradient(controls, fault):
    sound = controls["grads", "operands_bf16"]
    assert sound["correct"] and sound["worst_leaf_rel"] < 0.05
    reading = controls["grads", fault]
    assert not reading["sound"]
    # clean on clean by token changes what the clean half computes, which
    # the noisy half reads two layers on: seen, and small
    factor = 1 if fault == "mask_clean_token_causal" else 10
    assert reading["worst_leaf_rel"] > factor * sound["worst_leaf_rel"]


def test_the_loss_only_faults_are_not_run_on_the_logits(controls):
    assert ("logits", "weights_dropped") not in controls
    assert ("logits", "loss_over_all_positions") not in controls
    assert ("mask_probe", "no_qk_norm") not in controls


def test_kernel_costs_follow_their_shapes():
    # 64 tiles of 128 a half: the noisy diagonal, and a lower triangle with
    # its diagonal in each of noisy-on-clean and clean-on-clean
    tiles = 64 + 2 * (64 * 65 // 2)
    assert sdar_ref.visible_tiles(8192, 4) == tiles == 4224
    assert sdar_ref.visible_pairs(8192, 4) == 8192 * 4 + 8192 * 8192
    assert sdar_ref.visible_pairs(8, 4) == 2 * 16 + 16 + 16 + 32
    fwd, nbytes = sdar_ref.flash_cost("flash_fwd", SZ, 1, 8192)
    # QK^T and PV over 128 lanes, 2 FLOPs a multiply-accumulate
    assert fwd == 2 * (128 + 128) * tiles * 128 * 128 * 32
    # q and o over 32 heads, k and v over 4, the float32 row statistics,
    # all over 16,384 rows
    assert nbytes == 2 * 16384 * 128 * (2 * 32 + 2 * 4) + 4 * 32 * 16384
    dq, _ = sdar_ref.flash_cost("flash_dq", SZ, 1, 8192)
    dkv, _ = sdar_ref.flash_cost("flash_dkv", SZ, 1, 8192)
    assert (dq, dkv) == (fwd // 4 * 6, fwd // 4 * 8)
    flops, nbytes = sdar_ref.grouped_cost(16384, SZ)
    assert flops == 2 * 16384 * 3 * 2048 * 768
    assert nbytes == 2 * (2 * 16384 * 2048 + 4 * 16384 * 768
                          + 16 * 3 * 2048 * 768)
    # the kernels' own tiles of 512 hold 9 % more pairs than the
    # definition's tiles of 128: that is charged to the kernels
    from caffe_mpi_tpu.ops.flash_attention import tile_counts
    visited, _ = tile_counts(16384, 16384, False, bd=(8192, 4))
    assert 1.08 < visited * 512 * 512 / (tiles * 128 * 128) < 1.10


def test_the_six_readers_return_a_value_or_none_and_never_raise():
    import run as harness
    record = {"cell": CELL, "traced_iters": 0, "peaks": {},
              "bd_sizes": sdar_ref.sizes_record(SZ),
              "bd_rows": [[1024.0] * 15 + [2048.0]] * 5,
              "bd_rows_after": [[1024.0] * 16] * 5}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted(
        f"bd_{m}" for m in ("attention_ms_per_step", "flash_roofline",
                            "moe_ms_per_step", "experts_roofline",
                            "rows_max_over_mean", "noise_ms_per_step"))
    for name in mine:
        reader = harness.load_module(BENCH / "layer_metrics" / f"{name}.py")
        for run_record in (record, {"cell": CELL}):
            value = reader.compute(run_record, None)
            if name == "bd_rows_max_over_mean" and run_record is record:
                assert abs(value - 2048 / (17 * 1024 / 16)) < 1e-9
            else:
                assert value is None, name


def test_the_new_cell_rehearses_through_the_command():
    proc, lines = run_cell(ROOT, "--workload", CELL, "--seed", "3000000001",
                           "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["mask_probe"]["ok"]
    assert checks["logits"]["ok"] and checks["logits"]["seq_len"] == 32
    assert checks["logits"]["noise_ok"]
    # the gradient check ran on every leaf that trains: 33 less 3 routers
    assert checks["grads"]["ok"] and checks["grads"]["leaves"] == 30
    assert checks["grads"]["loss_rel"] < checks["grads"]["loss_rel_max"]
    assert checks["grads"]["frozen"] == [f"blk{l}/moe/gate"
                                         for l in range(3)]
    assert len(checks["logits"]["bd_rows"]) == 3
    # 4 choices of each of 64 rows went somewhere; the two held experts
    # got at most all of them
    assert all(0 <= sum(rows) <= 256 for rows in checks["logits"]["bd_rows"])
    assert checks["masked_share"]["ok"]
    readers = next(line for line in lines
                   if "layer_metric_readers_with_a_value" in line)
    assert "bd_rows_max_over_mean" in \
        readers["layer_metric_readers_with_a_value"]
