"""The state-space reference (`reference/nemotron_ref.py`) and its counts
against the program, on the CPU: parameters and multiply-accumulates
against the built `Net` at the published widths (built, never initialised),
the configuration against the catalog's row, the reference against the
`Net` at the rehearsal preset, the blocked loss, the controls, the counting
functions on cases worked by hand, the ten readers on a record without a
trace and on a hand-made one, and the cell's rehearsal through the
command."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT
from reference import nemotron_ref
from test_bench_command import run_cell

CELL = "nemotron3_nano_bf16_s8k_ep16share"
CONFIG = json.loads(
    (BENCH / "configs" / "nemotron3_nano_30b_a3b.json").read_text())
SZ = nemotron_ref.sizes_from_config(CONFIG)
TINY = nemotron_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY_NET = CONFIG["rehearse"]["solver"].replace("tiny_solver",
                                                "tiny_train_val")
READERS = ["ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
           "nemotron_attention_ms_per_step", "nemotron_flash_roofline",
           "nemotron_moe_ms_per_step", "nemotron_experts_roofline",
           "nemotron_rows_max_over_mean", "nemotron_head_ms_per_step",
           "nemotron_shared_expert_ms_per_step"]


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
    sizes = CONFIG["sizes"]
    # the issue's own arithmetic: input product, output product, the
    # convolution with its bias, A_log / D / dt_bias, the gate norm
    assert by_layer["blk0/ssm"] + by_layer["blk0/norm"] == 38_744_896 \
        == 2688 * 10304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096 \
        + 2688 == sizes["per_layer_parameters"]["M"]
    assert by_layer["blk5/attn"] + by_layer["blk5/norm"] == 23_399_040 \
        == sizes["per_layer_parameters"]["*"]
    # router and bias, 8 experts of two matrices, the shared expert
    assert by_layer["blk1/moe"] == 2688 * 128 + 128 \
        + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712
    assert by_layer["blk1/moe"] + by_layer["blk1/norm"] \
        == sizes["per_layer_parameters"]["E"]
    assert by_layer["embed"] + by_layer["logits"] + by_layer["ln_f"] \
        == sizes["table_head_and_final_norm_parameters"] \
        == 2 * 16384 * 2688 + 2688
    total = sum(by_layer.values())
    assert total == nemotron_ref.param_count(SZ) == 666_963_456 \
        == sizes["learnable_parameters"]
    assert sizes["argument_bytes_at_12_a_parameter"] == 12 * total
    macs = nemotron_ref.macs_per_sample(SZ, 8192)
    assert macs == net_macs_per_image(net) \
        == sizes["forward_macs_per_sequence_of_8192"]
    a_token = sizes["forward_macs_per_token"]
    assert a_token["total"] == macs // 8192 == 4 * a_token["M"] \
        + 4 * a_token["E"] + a_token["*"] + a_token["head"]
    # the four Mamba-2 layers are 45 % of the forward multiply-accumulates
    assert 0.44 < 4 * a_token["M"] / a_token["total"] < 0.45
    assert a_token["of_which_the_recurrence_by_its_definition"] \
        == 2 * 4096 * 128
    # the whole model by the same count: 31.58 B
    assert sizes["whole_model_parameters"] == 31_577_940_288


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
    # the pattern is cut with the depth: its first nine letters
    assert differs == set(CONFIG["reduced"]) | {"hybrid_override_pattern"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert {k: row[k] for k in differs} == {
        k: CONFIG["published"][k] for k in differs}
    pattern = CONFIG["hybrid_override_pattern"]
    assert row["hybrid_override_pattern"].startswith(pattern)
    assert len(pattern) == CONFIG["num_hidden_layers"] == 9
    # a whole period of the pattern and at least four layers after
    assert "EMEMEM*" in pattern[:7] * 2 and {*pattern} == {"M", "E", "*"}
    assert CONFIG["vocab_size"] * 8 == row["vocab_size"]
    assert CONFIG["n_routed_experts"] == 8
    assert SZ.experts == row["n_routed_experts"] == 128
    assert (SZ.top_k, SZ.scaling) == (row["num_experts_per_tok"], 2.5)


def test_the_committed_recipes_are_what_the_generator_emits():
    import sys
    sys.path.insert(0, str(ROOT / "models"))
    import generate_models as g
    for net, sizes in (("train_val.prototxt", g.NEMOTRON),
                       ("tiny_train_val.prototxt", g.NEMOTRON_TINY)):
        text = g.nemotron_h(**sizes, remat=g.NEMOTRON_REMAT).to_prototxt()
        assert (ROOT / "models" / "nemotron3_nano_30b_a3b" / net
                ).read_text() == text + "\n"
    assert g.NEMOTRON["pattern"] == CONFIG["hybrid_override_pattern"]
    assert g.NEMOTRON["vocab"] == CONFIG["vocab_size"]


@pytest.fixture(scope="module")
def tiny_case():
    net = built(TINY_NET)
    params, state = net.init(jax.random.PRNGKey(1))
    shape = net.feed_specs["tokens"][0]
    tokens = jax.random.randint(jax.random.PRNGKey(2), shape, 0, TINY.vocab)
    return net, params, state, {"tokens": tokens,
                                "label": jnp.roll(tokens, -1, axis=1)}


@pytest.mark.parametrize("precision,low,high", [("f32", 0.0, 1e-5),
                                                ("bf16", 1e-4, 3e-2)])
def test_reference_agrees_with_the_net_at_the_rehearsal_preset(
        tiny_case, precision, low, high):
    """f32 to rounding; bf16 off by about its own rounding, which a
    tolerance between the two tells from f32."""
    _, params, state, feeds = tiny_case
    net = built(TINY_NET, precision)
    blobs, _, loss = net.apply(params, state, feeds, train=True,
                               rng=jax.random.PRNGKey(3))
    ref = nemotron_ref.from_net(params, TINY)
    want = np.asarray(nemotron_ref.forward(ref, feeds["tokens"], TINY,
                                           q_block=16), np.float64)
    got = np.asarray(blobs["logits"].astype(jnp.float32), np.float64)
    assert got.shape == want.shape == (*feeds["tokens"].shape, TINY.vocab)
    assert low <= np.linalg.norm(got - want) / np.linalg.norm(want) < high
    want_loss = float(nemotron_ref.loss(ref, feeds["tokens"],
                                        feeds["label"], TINY))
    assert abs(float(loss) - want_loss) < max(high, 1e-5) * want_loss


@pytest.mark.parametrize("vocab_block,time_block", [(24, 8), (32, 4),
                                                    (64, 32)])
def test_the_blocked_loss_is_the_loss(tiny_case, vocab_block, time_block):
    """Value and gradient, with the vocabulary in blocks that do and do not
    divide it and the recurrence in blocks of time."""
    _, params, _, feeds = tiny_case
    plain = jax.value_and_grad(lambda p: nemotron_ref.loss(
        nemotron_ref.from_net(p, TINY), feeds["tokens"], feeds["label"],
        TINY))
    blocked = jax.value_and_grad(lambda p: nemotron_ref.loss_blocked(
        nemotron_ref.from_net(p, TINY), feeds["tokens"], feeds["label"],
        TINY, 16, vocab_block, time_block=time_block))
    (l0, g0), (l1, g1) = plain(params), blocked(params)
    assert abs(float(l1) / float(l0) - 1) < 1e-5
    for layer in ("embed", "logits", "blk0/ssm", "blk3/attn", "blk4/moe"):
        for blob, want in g0[layer].items():
            if float(jnp.linalg.norm(want)):
                assert float(jnp.linalg.norm(g1[layer][blob] - want)
                             / jnp.linalg.norm(want)) < 1e-4, (layer, blob)


def test_an_eight_bit_product_stands_well_outside_bf16(tiny_case):
    _, params, _, feeds = tiny_case
    ref = nemotron_ref.from_net(params, TINY)
    want = nemotron_ref.forward(ref, feeds["tokens"], TINY)
    off = lambda dt: float(jnp.linalg.norm(nemotron_ref.forward(
        ref, feeds["tokens"], TINY, operand_dtype=dt) - want)
        / jnp.linalg.norm(want))
    assert 0 < off(jnp.bfloat16) < off(jnp.float8_e4m3fn) / 4


@pytest.fixture(scope="module")
def controls():
    """The driver's `--controls` mode at the rehearsal preset: the
    reference with one fault planted where the program stands, through
    the same comparisons as the set-up checks."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_ssm_lm.py")
    lines = []
    driver.controls(harness.load_cell(CELL, rehearse=True), 7,
                    lambda **fields: lines.append(fields))
    return ({(line["control"], line["fault"]): line
             for line in lines if "control" in line}, driver.faults())


# the issue's list, in its order
FAULTS = ["state_dropped_at_chunk_edges", "decay_arithmetic_in_bf16",
          "no_softplus", "d_left_out", "gate_after_the_norm",
          "one_norm_over_every_channel", "group_by_remainder",
          "a_tap_on_the_next_row", "relu_for_relu_squared", "a_gated_expert",
          "no_scaling_factor", "weights_not_renormalised", "rotary_applied",
          "operands_f8_e4m3"]


def test_the_controls_are_the_issue_s(controls):
    _, faults = controls
    assert sorted(faults) == sorted(FAULTS + ["operands_bf16"])
    assert [name for name, (sound, _) in faults.items() if sound] \
        == ["operands_bf16"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_logits(controls, fault):
    """At the tiny size the distances are small (a mixer of gaussian 0.02
    products over 48 channels adds little to a unit-normal table's rows),
    so the limits that are set on the chip at the timed size do not apply;
    that each fault is seen does."""
    readings, _ = controls
    sound = readings["logits", "operands_bf16"]
    assert sound["sound"] and sound["correct"]
    reading = readings["logits", fault]
    # (without its softplus a decay passes one and the state overflows:
    # not a number is seen too)
    assert not reading["sound"] and not reading["rel_rms"] <= 0
    if fault.startswith("operands"):
        assert reading["rel_rms"] > 4 * sound["rel_rms"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_a_leaf_s_gradient(controls, fault):
    readings, _ = controls
    sound = readings["grads", "operands_bf16"]
    assert sound["correct"] and sound["whole_rel"] < 0.05
    reading = readings["grads", fault]
    assert not reading["sound"]
    assert not max(reading["largest_against_its_own_norm"].values()) <= 1e-3


def test_the_scan_s_probe_judges_the_faults_of_the_scan(controls):
    """At the rehearsal's 32 positions no decay has room to drift; that
    the probe runs the four faults whose term its reference reads, and
    no other, does show."""
    readings, faults = controls
    judged = sorted(fault for kind, fault in readings if kind == "scan_probe")
    assert judged == ["decay_arithmetic_in_bf16", "group_by_remainder",
                      "no_softplus", "state_dropped_at_chunk_edges"]
    for fault in ("group_by_remainder", "state_dropped_at_chunk_edges"):
        assert not readings["scan_probe", fault]["correct"]


def test_the_scan_s_probe_tells_bf16_decays_from_float32_ones():
    """The published scan (64 heads of 64 x 128, chunks of 128) over 1,024
    positions, the probe's inputs: the program reads a rounding of its bf16
    operands on every head; a decay, a time step and a state rounded to
    bf16 drift on the slow heads (the limit is set on the chip at 8,192
    positions, where they drift further)."""
    import run as harness
    driver = harness.load_module(BENCH / "drivers" / "train_ssm_lm.py")
    cell = driver.lm.with_preset(harness.load_cell(CELL, rehearse=False))
    key = jax.random.PRNGKey(11)
    probe = lambda how=None: driver.scan_probe(cell, SZ, 1024, key, "bf16",
                                               how)
    sound = probe()
    assert sound["ok"] and sound["worst_head_rel"] < 0.004
    assert sound["worst_head_rel"] < 1.5 * sound["median_head_rel"]
    wrong = probe({"decay_dtype": jnp.bfloat16})
    assert not wrong["ok"]
    assert wrong["worst_head_rel"] > 10 * sound["worst_head_rel"]
    # most heads decay fast and hardly show it: the worst head decides
    assert wrong["median_head_rel"] < 3 * sound["median_head_rel"]


def test_kernel_costs_follow_their_shapes():
    # 64 tiles of 128 a side: the lower triangle with its diagonal
    tiles = 64 * 65 // 2
    assert nemotron_ref.visible_tiles(8192) == tiles == 2080 \
        == CONFIG["sizes"]["visible_128_tiles_per_head"]
    assert nemotron_ref.visible_pairs(8192) == 8192 * 8193 // 2 \
        == CONFIG["sizes"]["visible_pairs_per_head"]
    fwd, nbytes = nemotron_ref.flash_cost("flash_fwd", SZ, 1, 8192)
    # QK^T and PV over 128 lanes, 2 FLOPs a multiply-accumulate, 32 heads
    assert fwd == 2 * (128 + 128) * tiles * 128 * 128 * 32
    # q and o over 32 heads, k and v over 2, the float32 row statistics
    assert nbytes == 2 * 8192 * 128 * (2 * 32 + 2 * 2) + 4 * 32 * 8192
    dq, _ = nemotron_ref.flash_cost("flash_dq", SZ, 1, 8192)
    dkv, _ = nemotron_ref.flash_cost("flash_dkv", SZ, 1, 8192)
    assert (dq, dkv) == (fwd // 4 * 6, fwd // 4 * 8)
    # an ungated expert: two matrices
    flops, nbytes = nemotron_ref.grouped_cost(3072, SZ)
    assert flops == 2 * 3072 * 2 * 2688 * 1856
    assert nbytes == 2 * (2 * 3072 * 2688 + 2 * 3072 * 1856
                          + 8 * 2 * 2688 * 1856)
    # at 384 rows an expert the products' bytes (the banks', mostly) take
    # 0.85 of the time of their FLOPs: the two bounds lie close
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    assert 0.8 < (nbytes / peaks["hbm_bytes_per_s"]["value"]) \
        / (flops / peaks["bf16_flops_per_s"]["value"]) < 0.9
    # the scan by its definition: 2 H P N multiply-accumulates a position,
    # x, B, C, delta and y once; the backward pass twice that again; its
    # bytes bind (0.62 ms a layer against 0.26 of FLOPs)
    flops, nbytes = nemotron_ref.scan_cost(SZ, 1, 8192)
    assert flops == 3 * 2 * (2 * 4096 * 128) * 8192 \
        == CONFIG["sizes"]["scan_cost_a_layer_forward_and_backward"]["flops"]
    assert nbytes == 3 * 2 * (2 * 4096 + 2 * 1024 + 64) * 8192 \
        == CONFIG["sizes"]["scan_cost_a_layer_forward_and_backward"]["bytes"]
    assert nbytes / peaks["hbm_bytes_per_s"]["value"] \
        > 2 * flops / peaks["bf16_flops_per_s"]["value"]
    # `macs_per_sample` (what `mfu` reads) counts the same definition
    assert 2 * nemotron_ref.scan_macs_per_token(SZ) * 8192 * 3 == flops


def read(metric: str, run: dict, trace):
    import run as harness
    return harness.load_module(
        BENCH / "layer_metrics" / f"{metric}.py").compute(run, trace)


def test_the_cell_s_readers_are_these_ten():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(READERS)
    assert {m["moves"] for m in mine} == {"train_samples_per_s"}
    assert all(m["unit"] == "%" for m in mine
               if m["name"].endswith("_roofline"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


RECORD = {"cell": CELL, "traced_iters": 0, "peaks": {},
          "samples_per_iter": 1, "seq_len": 8192,
          "nemotron_sizes": nemotron_ref.sizes_record(SZ),
          "nemotron_rows": [[384.0] * 7 + [768.0]] * 4,
          "nemotron_rows_after": [[384.0] * 8] * 4}


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_without_a_trace_returns_none_and_never_raises(metric):
    """On this cell's record and on another program's (the parent's: no
    such rows, no such sizes)."""
    for run_record in (RECORD, {"cell": CELL},
                       {"cell": CELL, "traced_iters": 10}):
        value = read(metric, run_record, None)
        if metric == "nemotron_rows_max_over_mean" and run_record is RECORD:
            assert abs(value - 768 / (9 * 384 / 8)) < 1e-9
        else:
            assert value is None, metric


def test_the_rooflines_divide_the_definition_s_least_time(monkeypatch):
    """With the scopes' and the kernels' seconds handed in: the scan's
    share is four layers' bytes-bound least time over the time under
    `ssm.scan`; the flash share one attention layer's three kernels."""
    import scope_reduce
    peaks = {k: v["value"] for k, v in json.loads(
        (BENCH / "peaks.json").read_text())["TPU v5 lite"].items()}
    run = {**RECORD, "traced_iters": 5, "peaks": peaks}
    monkeypatch.setattr(scope_reduce, "for_run",
                        lambda run, trace, scope: 0.1)
    nbytes = nemotron_ref.scan_cost(SZ, 1, 8192)[1]
    least = 4 * nbytes / peaks["hbm_bytes_per_s"]
    assert abs(read("ssm_scan_roofline", run, {}) - 100 * least * 5 / 0.1) \
        < 1e-9
    assert abs(read("ssm_scan_ms_per_step", run, {}) - 20.0) < 1e-9
    assert abs(read("nemotron_shared_expert_ms_per_step", run, {}) - 20.0) \
        < 1e-9
    assert 0 < read("nemotron_experts_roofline", run, {}) < 100
    trace = {"custom_calls": {k: {"seconds": 0.05, "count": 5} for k in (
        "flash_fwd", "flash_dq", "flash_dkv", "gmm")}}
    want = sum(nemotron_ref.flash_cost(k, SZ, 1, 8192)[0]
               for k in ("flash_fwd", "flash_dq", "flash_dkv")) \
        / peaks["bf16_flops_per_s"]
    assert abs(read("nemotron_flash_roofline", run, trace)
               - 100 * want * 5 / 0.15) < 1e-9


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_new_cell_rehearses_through_the_command(trace):
    proc, lines = run_cell(ROOT, "--workload", CELL, "--seed", "3000000001",
                           "--seconds", "0.5", "--trace", trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["metrics"] == {} and last["correct"] is True
    assert last["failed"] == 0
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["logits"]["ok"] and checks["logits"]["seq_len"] == 32
    # the gradient check ran on every leaf that trains: table, head, last
    # norm, five pre-norms, two Mamba-2 layers' 8 blobs, one attention
    # layer's 2, two expert layers' 4
    assert checks["grads"]["ok"] \
        and checks["grads"]["leaves"] == 3 + 5 + 2 * 8 + 2 + 2 * 4
    assert checks["grads"]["frozen"] == 2 * 2
    assert checks["scan_probe"]["ok"] and checks["scan_probe"]["finite"]
    assert checks["loss"]["ok"]
    # the first loss reads about ln(vocabulary)
    assert 0.9 * math.log(64) < checks["loss"]["first"] < 1.3 * math.log(64)
    # only the pattern's E layers count rows
    assert len(checks["logits"]["nemotron_rows"]) == 2
    assert all(0 <= sum(rows) <= 4 * 32
               for rows in checks["logits"]["nemotron_rows"])
    if trace == "1":
        readers = next(line for line in lines
                       if "layer_metric_readers_with_a_value" in line)
        assert "nemotron_rows_max_over_mean" in \
            readers["layer_metric_readers_with_a_value"]
