"""NVIDIA-Nemotron-3-Nano-30B-A3B's training step through prototxt -> Net,
at a tiny size on the CPU, against the benchmark's plain reference
(benchmarks/reference/nemotron_ref.py): D 48, pattern MEM*E, 4 Mamba-2 heads
of 8 lanes with a state of 16 in 2 groups and chunks of 8, 4 query heads
over 2 key/value heads of 16, one sequence of 32, 32 experts (2 held, 4 a
token, width 32, ungated relu^2) with a shared one of 64, vocabulary 64 —
the sizes of `models/nemotron3_nano_30b_a3b/tiny_train_val.prototxt`, which
the same generator emits as the benchmark's recipe.

The reference framework (a CNN-era Caffe) has neither a state-space layer,
attention nor experts (SURVEY §5.7, §2.7): there is no analogue to cite.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmarks"),
                            os.path.join(ROOT, "models"))
                if p not in sys.path]

from reference import nemotron_ref  # noqa: E402

from caffe_mpi_tpu.layers.sequence import (MAMBA2_INPUT,  # noqa: E402
                                           MAMBA2_SCAN)
from caffe_mpi_tpu.net import Net  # noqa: E402
from caffe_mpi_tpu.ops import moe as moe_ops  # noqa: E402
from caffe_mpi_tpu.ops.flash_attention import KEPT_UNDER_REMAT  # noqa: E402
from caffe_mpi_tpu.ops.ssd import KEPT, ssd  # noqa: E402
from caffe_mpi_tpu.proto import NetParameter  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "nemotron3_nano_30b_a3b.json")))
SZ = nemotron_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
MODELS = os.path.join(ROOT, "models", "nemotron3_nano_30b_a3b")
S = 32


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def net_from(text: str) -> Net:
    return Net(NetParameter.from_text(text), phase="TRAIN", precision="f32")


# -- the Mamba-2 layer alone ------------------------------------------------

# fillers an order above the recipe's, so that no term is small
SSM = """
layer { name: "in" type: "Input" top: "x"
        input_param { shape { dim: 2 dim: %d dim: 48 } } }
layer { name: "ssm" type: "Mamba2" bottom: "x" top: "y"
  mamba2_param { num_heads: 4 head_dim: 8 state_size: 16 groups: 2
    conv_kernel: 4 chunk: %d eps: 1e-5 %s
    weight_filler { type: "gaussian" std: 0.3 } } }"""


def ssm_net(seq: int = S, chunk: int = 8, extra: str = "") -> Net:
    return net_from(SSM % (seq, chunk, extra))


def reference_weights(p: dict) -> dict:
    """One Mamba-2 layer's blobs as `nemotron_ref.mamba` reads them (what
    `nemotron_ref.from_net` does for a whole net)."""
    f = lambda name: jnp.asarray(p[name], jnp.float32)
    return {"w_in": f("in_weight").T, "conv": f("conv_weight"),
            "conv_bias": f("conv_bias"), "dt_bias": f("dt_bias"),
            "a_log": f("A_log"), "d": f("D"), "w_n": f("norm_scale"),
            "w_out": f("out_weight").T}


def ssm_case(seq: int):
    params, _ = ssm_net(seq).init(jax.random.PRNGKey(2))
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    # the vectors start at constants: move them, so that each is seen; dt's
    # bias up to where decays are neither all 1 nor all 0
    p = dict(params["ssm"])
    p["conv_bias"] = 0.3 * jax.random.normal(keys[0], p["conv_bias"].shape)
    p["dt_bias"] = jax.random.normal(keys[1], (4,)) - 1.0
    p["D"] = 1.0 + 0.5 * jax.random.normal(keys[2], (4,))
    p["norm_scale"] = 1.0 + 0.3 * jax.random.normal(keys[3],
                                                    p["norm_scale"].shape)
    x = jax.random.normal(keys[4], (2, seq, 48))
    cot = jax.random.normal(keys[5], (2, seq, 48))
    return {"ssm": p}, x, cot


def layer_out(net, params, x):
    return net.apply(params, {}, {"x": x}, train=True,
                     rng=jax.random.PRNGKey(0))[0]["y"]


# one chunk, two, several, one shorter than a chunk; chunk sizes that differ
@pytest.mark.parametrize("seq,chunk", [(8, 8), (16, 8), (32, 8), (32, 4),
                                       (32, 16), (32, 32), (24, 128)])
def test_the_layer_is_the_reference_s_recurrence(seq, chunk):
    """Equations M 1-7 in float32, forward and every blob's gradient,
    against the recurrence one position a step: the chunked form is the
    same function whatever the chunk."""
    params, x, cot = ssm_case(seq)
    net = ssm_net(seq, chunk)
    sz = dataclasses.replace(SZ, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda p, x: layer_out(net, p, x), params, x)
        want, ref_vjp = jax.vjp(lambda p, x: nemotron_ref.mamba(
            reference_weights(p["ssm"]), x, sz), params, x)
        (g_p, g_x), (w_p, w_x) = vjp(cot), ref_vjp(cot)
    assert rel(got, want) < 2e-5
    assert rel(g_x, w_x) < 2e-4
    assert set(w_p["ssm"]) == {
        "in_weight", "conv_weight", "conv_bias", "dt_bias", "A_log", "D",
        "norm_scale", "out_weight"}
    for blob, grad in w_p["ssm"].items():
        assert rel(g_p["ssm"][blob], grad) < 2e-4, blob


@pytest.mark.parametrize("chunk", [8, 32])
def test_remat_changes_no_number_of_the_layer(chunk):
    """Under `remat: true` a Mamba2 layer keeps its input product, its
    scan's output and the scan's carried states (`kept_under_remat`) and
    computes the rest again: the value and the gradient with respect to
    every blob and the bottom are the layer's without it."""
    params, x, cot = ssm_case(S)
    text = SSM % (S, chunk, "")
    nets = [net_from(text),
            net_from(text.replace('top: "y"', 'top: "y" remat: true'))]
    assert nets[1]._layer_by_name("ssm").lp.remat
    (y0, (p0, x0)), (y1, (p1, x1)) = (
        (y, vjp(cot)) for y, vjp in (
            jax.vjp(lambda p, x, net=net: layer_out(net, p, x), params, x)
            for net in nets))
    assert rel(y1, y0) < 1e-6
    assert rel(x1, x0) < 1e-6
    for blob, grad in p0["ssm"].items():
        assert rel(p1["ssm"][blob], grad) < 1e-6, blob


DENSE = 'type: "InnerProduct" inner_product_param { num_output: 48 axis: 2 }'
ATTENTION = 'type: "Attention" attention_param { num_heads: 4 causal: true }'
MAMBA2 = ('type: "Mamba2" mamba2_param { num_heads: 4 head_dim: 8 '
          'state_size: 16 groups: 2 conv_kernel: 4 chunk: 8 }')


def one_layer_net(layer: str) -> Net:
    return net_from(f"""
layer {{ name: "in" type: "Input" top: "x"
        input_param {{ shape {{ dim: 2 dim: {S} dim: 48 }} }} }}
layer {{ name: "l" bottom: "x" top: "y" remat: true {layer} }}""")


@pytest.mark.parametrize("layer,kept", [
    (DENSE, ()),
    (ATTENTION, KEPT_UNDER_REMAT),
    (MAMBA2, (MAMBA2_INPUT, MAMBA2_SCAN, KEPT)),
    # a block that holds an attention layer keeps what that layer keeps
    ('type: "Pipeline" pipeline_param { num_stages: 2 layer { name: "a" '
     f'bottom: "x" top: "a" {ATTENTION} }} }}', KEPT_UNDER_REMAT)])
def test_each_type_keeps_what_its_backward_pass_reads(layer, kept):
    assert one_layer_net(layer)._layer_by_name("l").kept_under_remat == kept


@pytest.mark.parametrize("layer,keeps", [
    (DENSE, False), ('type: "RMSNorm"', False),
    # the jnp path writes no flash name, though the type keeps them
    (ATTENTION, False), (MAMBA2, True)])
def test_a_layer_that_writes_no_kept_name_is_remat_that_keeps_nothing(
        layer, keeps):
    """`remat: true` on a layer that writes none of the names its type keeps
    lowers to the text of a checkpoint that keeps nothing: the policy
    changes the program only where a name is written (a Mamba2 layer)."""
    net = one_layer_net(layer)
    params, _ = net.init(jax.random.PRNGKey(0))
    one = net._layer_by_name("l")
    nothing = jax.checkpoint(
        lambda p, b: one.apply(p, {}, b, train=True, rng=None)[0],
        policy=jax.checkpoint_policies.save_only_these_names())
    x = jnp.zeros((2, S, 48))
    texts = [jax.jit(lambda p, x, f=f: jax.vjp(f, p, x)[1](x)).lower(
        params, x).as_text() for f in (
            lambda p, x: net.apply(p, {}, {"x": x}, train=True)[0]["y"],
            lambda p, x: nothing(p["l"], [x])[0])]
    assert (texts[0] != texts[1]) == keeps


def test_the_reference_s_time_blocks_are_its_scan():
    params, x, cot = ssm_case(S)
    lp = reference_weights(params["ssm"])
    with jax.default_matmul_precision("highest"):
        run = lambda block: jax.vjp(
            lambda x: nemotron_ref.mamba(lp, x, SZ, block), x)
        (whole, vjp), (blocked, blocked_vjp) = run(None), run(4)
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(blocked_vjp(cot)[0], vjp(cot)[0], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("seq,chunk", [(8, 8), (32, 8), (64, 16)])
def test_the_scan_alone_is_the_reference_s_on_the_probe_s_inputs(seq, chunk):
    """What the benchmark's `scan_probe` compares at the timed size:
    `ops/ssd.py` against equations M 4-5 one position a step, on inputs
    whose time step does not move with the position and with no D term."""
    x, raw, dt_bias, a_log, b, c = nemotron_ref.probe_inputs(
        jax.random.PRNGKey(seq), seq, SZ, (0.001, 0.1, 1e-4), jnp.float32)
    assert not np.asarray(raw).any() and x.shape == (1, seq, 4, 8)
    step = np.asarray(jax.nn.softplus(dt_bias))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    np.testing.assert_allclose(a_log, np.log([1.0, 2.0, 3.0, 4.0]),
                               rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        got = ssd(x, raw, a_log, b, c, jnp.zeros(4), dt_bias, chunk)
        want = nemotron_ref.scan(x, raw, dt_bias, a_log, b, c, chunk)
        dropped = nemotron_ref.scan(x, raw, dt_bias, a_log, b, c, chunk,
                                    carry_state=False)
    assert rel(got, want) < 2e-5
    assert (rel(dropped, want) > 0.1) == (seq > chunk)


SSM_FAULTS = ["carry_state", "softplus", "d_term", "gate_first",
              "norm_in_groups", "group_by_division", "causal_taps"]


@pytest.mark.parametrize("fault", SSM_FAULTS + ["decay_dtype"])
def test_no_term_of_the_reference_s_mixer_is_dead(fault):
    """The reference with one term changed alone is another function: the
    layer, which agrees with the sound one, is not near it."""
    params, x, _ = ssm_case(S)
    got = layer_out(ssm_net(), params, x)
    how = {fault: jnp.bfloat16} if fault == "decay_dtype" \
        else {fault: not nemotron_ref.FAULTS[fault]}
    planted = nemotron_ref.mamba(reference_weights(params["ssm"]), x, SZ,
                                 **how)
    assert not rel(got, planted) < (1e-3 if fault == "decay_dtype" else 1e-2)


@pytest.mark.parametrize("t", [1, 7, 8, 17, 31])
def test_what_comes_later_leaves_a_position_bit_identical(t):
    """Causality to the bit, inside a chunk and across chunk edges: the
    masked entries of a chunk's matrix are exact zeros and a chunk's state
    goes forward only."""
    params, x, _ = ssm_case(S)
    net = ssm_net()
    later = x.at[:, t:].set(jax.random.normal(jax.random.PRNGKey(9),
                                              x[:, t:].shape))
    np.testing.assert_array_equal(layer_out(net, params, later)[:, :t],
                                  layer_out(net, params, x)[:, :t])
    assert not np.array_equal(layer_out(net, params, later)[:, t:],
                              layer_out(net, params, x)[:, t:])


def test_the_scan_keeps_its_decays_in_float32_under_bf16_operands():
    """bf16 operands, float32 decays and states: near the float32 result,
    where decays rounded to bf16 (the reference's planted fault) are not."""
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    n, s, h, p, g, st = 1, 256, 4, 8, 2, 16
    x = jax.random.normal(keys[0], (n, s, h, p))
    dt = jax.random.normal(keys[1], (n, s, h)) - 6.0   # long memories
    b, c = (jax.random.normal(k, (n, s, g, st)) for k in keys[2:4])
    # D = 0: what is compared is what went through the state
    vec = (jnp.log(jnp.arange(1.0, h + 1)), jnp.zeros(h), jnp.zeros(h))
    run = lambda cast: ssd(cast(x), cast(dt), vec[0], cast(b), cast(c),
                           vec[1], vec[2], 32)
    want = run(lambda t: t)
    got = run(lambda t: t.astype(jnp.bfloat16)).astype(jnp.float32)
    assert rel(got, want) < 2e-2
    delta = jax.nn.softplus(dt)
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    a = low(jnp.exp(low(low(delta) * -jnp.exp(vec[0]))))
    rounded = nemotron_ref.recurrence(x, a, low(delta), b, c,
                                      state_dtype=jnp.bfloat16)
    assert rel(rounded, want) > 1.5 * rel(got, want)


@pytest.mark.parametrize("text,match", [
    ("num_heads: 4 head_dim: 6 state_size: 16 groups: 3",
     "4 heads do not divide into 3 groups"),
    ("num_heads: 4 head_dim: 8 state_size: 16 groups: 2 chunk: 12",
     "a sequence of 32 positions is not whole chunks of 12"),
    ("num_heads: 6 head_dim: 3 state_size: 16 groups: 4",
     "the inner width 6 x 3 does not divide into 4 groups"),
    ("num_heads: 4 head_dim: 8", "mamba2_param needs num_heads, head_dim, "
                                 "state_size"),
])
def test_the_layer_refuses_what_has_no_meaning(text, match):
    """Each refusal is spelled once, in proto/netshape.py: the layer raises
    it and the static analysis reports it."""
    from caffe_mpi_tpu.proto.netshape import analyze_net
    net = """
layer { name: "in" type: "Input" top: "x"
        input_param { shape { dim: 1 dim: 32 dim: 48 } } }
layer { name: "ssm" type: "Mamba2" bottom: "x" top: "y"
        mamba2_param { %s } }""" % text
    with pytest.raises(ValueError, match=match):
        net_from(net)
    problems = analyze_net(NetParameter.from_text(net),
                           phase="TRAIN").problems
    assert any(match in p.message for p in problems), problems


def test_the_mixer_s_vectors_start_as_the_published_model_s():
    params, _ = ssm_net().init(jax.random.PRNGKey(0))
    p = params["ssm"]
    np.testing.assert_allclose(p["A_log"], np.log([1.0, 2.0, 3.0, 4.0]),
                               rtol=1e-6)
    np.testing.assert_array_equal(p["D"], 1.0)
    np.testing.assert_array_equal(p["norm_scale"], 1.0)
    np.testing.assert_array_equal(p["conv_bias"], 0.0)
    from caffe_mpi_tpu.core.fillers import fill
    from caffe_mpi_tpu.proto.config import FillerParameter
    wide = fill(FillerParameter(type="softplus_inverse_log_uniform",
                                min=0.001, max=0.1, value=1e-4),
                jax.random.PRNGKey(1), (4096,))
    step = np.asarray(jax.nn.softplus(wide))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    # log-uniform: the median is the geometric mean of the ends
    assert 0.008 < np.median(step) < 0.0125


@pytest.mark.parametrize("fields,low,high", [
    ("", 0.001, 0.1),                                 # the defaults
    ("dt_min: 0.02 dt_max: 0.5", 0.02, 0.5),
    ("dt_min: 0.001 dt_max: 0.004 dt_floor: 0.003", 0.003, 0.004)])
def test_the_time_step_s_start_is_the_recipe_s(fields, low, high):
    """`dt_min`, `dt_max`, `dt_floor` (a published config's time_step_min,
    _max, _floor) shape dt_bias's start and nothing else."""
    net = ssm_net(16, 8, fields)
    step = np.asarray(jax.nn.softplus(
        net.init(jax.random.PRNGKey(3))[0]["ssm"]["dt_bias"]))
    assert low * 0.999 <= step.min() and step.max() <= high * 1.001
    if "dt_floor" in fields:    # log-uniform in [0.001, 0.004]: most floored
        assert (np.abs(step - low) < 1e-6 * low).sum() >= 2


# -- the ungated expert layer -----------------------------------------------

MOE = """
layer { name: "in" type: "Input" top: "x"
        input_param { shape { dim: 2 dim: %d dim: 48 } } }
layer { name: "l" type: "MoE" bottom: "x" bottom: "x" top: "y" top: "rows"
        loss_weight: 0 loss_weight: 0
        propagate_down: true propagate_down: false
        moe_param { num_experts: 32 hidden_dim: 32 top_k: 4 dropless: true
                    experts_held: %d first_expert: %d scoring: "sigmoid"
                    routed_scaling_factor: 2.5 activation: "relu2"
                    gated: false shared_experts: 2
                    bias_filler { type: "gaussian" std: 0.05 }
                    gate_filler { type: "gaussian" std: %s }
                    weight_filler { type: "gaussian" std: 0.3 } } }"""


def reference_experts(p: dict) -> dict:
    return {"router": p["gate"], "select_bias": p["select_bias"],
            "up": p["w1"], "down": p["w2"], "s_up": p["shared_w1"],
            "s_down": p["shared_w2"]}


@pytest.fixture(scope="module")
def moe_case():
    whole = net_from(MOE % (32, 32, 0, 0.3))
    params, _ = whole.init(jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 32, 48))
    return whole, params, x


def test_an_ungated_layer_declares_two_banks(moe_case):
    _, params, _ = moe_case
    assert set(params["l"]) == {"gate", "select_bias", "w1", "w2",
                                "shared_w1", "shared_w2"}
    assert params["l"]["shared_w1"].shape == (48, 64)


# every expert held: the buffer of every pair. 2 of 32 over 256 rows (1,024
# pairs, a buffer of one tile of 512) under a flat router: the bounded
# branch; under a bias that sends them every row: the fallback
@pytest.mark.parametrize("held,seq,branch", [
    (32, 32, "all"), (2, 128, "bounded"), (2, 128, "fallback")])
def test_the_layer_is_the_reference_s_dense_expert_loops(moe_case, held,
                                                         seq, branch):
    """w2 relu(x w1)^2 under sigmoid scoring, the shared expert whole,
    forward and every blob's gradient, against dense per-expert loops."""
    _, params, _ = moe_case
    x = jax.random.normal(jax.random.PRNGKey(14), (2, seq, 48))
    mine = {**params["l"], **{k: params["l"][k][:held] for k in ("w1",
                                                                 "w2")}}
    if branch == "fallback":
        # the held experts' columns win every row
        mine["select_bias"] = mine["select_bias"].at[:held].set(10.0)
    net = net_from(MOE % (seq, held, 0, 0.3))
    sz = dataclasses.replace(SZ, experts_held=held)
    run = lambda p, x: net.apply({"l": p}, {}, {"x": x}, train=True,
                                 rng=jax.random.PRNGKey(0))[0]
    with jax.default_matmul_precision("highest"):
        rows = run(mine, x)["rows"]
        got, vjp = jax.vjp(lambda p, x: run(p, x)["y"], mine, x)
        want, ref_vjp = jax.vjp(lambda p, x: nemotron_ref.experts(
            reference_experts(p), x, sz), mine, x)
        (g_p, g_x), (w_p, w_x) = vjp(x), ref_vjp(x)
    pairs = 4 * 2 * seq
    bound = moe_ops._row_bound(pairs, held, 32)
    assert {"all": bound == pairs,
            "bounded": float(rows.sum()) < bound < pairs,
            "fallback": float(rows.sum()) >= bound}[branch], (rows, bound)
    assert rel(got, want) < 1e-5
    assert rel(g_x, w_x) < 1e-4
    for blob in ("w1", "w2", "shared_w1", "shared_w2"):
        assert rel(g_p[blob], w_p[blob]) < 1e-4, blob


@pytest.mark.parametrize("fault", ["squared", "gated_expert", "scaling",
                                   "renormalised"])
def test_no_term_of_the_reference_s_experts_is_dead(moe_case, fault):
    whole, params, x = moe_case
    got = whole.apply(params, {}, {"x": x}, train=True,
                      rng=jax.random.PRNGKey(0))[0]["y"]
    sz = dataclasses.replace(SZ, experts_held=32)
    planted = nemotron_ref.experts(
        reference_experts(params["l"]), x, sz,
        **{fault: not nemotron_ref.FAULTS[fault]})
    assert rel(got, planted) > 1e-2


def test_sixteen_shares_of_two_add_up_to_the_uncut_layer(moe_case):
    """THE share test: what experts 2 i, 2 i + 1 give on each of 16 chips,
    each as the layer computes its own share, with the shared expert, which
    every chip computes alike, counted ONCE, adds up to the uncut
    reference's layer, and every (token, choice) pair is counted once."""
    _, params, x = moe_case
    lp = reference_experts(params["l"])
    sz = dataclasses.replace(SZ, experts_held=32)
    with jax.default_matmul_precision("highest"):
        want = nemotron_ref.experts(lp, x, sz)
        shared = nemotron_ref.unit(x, lp["s_up"], lp["s_down"], None,
                                   nemotron_ref.FAULTS)
        total, rows = jnp.zeros_like(x), 0.0
        for first in range(0, 32, 2):
            mine = {**params["l"], **{k: params["l"][k][first:first + 2]
                                      for k in ("w1", "w2")}}
            blobs, _, _ = net_from(MOE % (32, 2, first, 0.3)).apply(
                {"l": mine}, {}, {"x": x}, train=True,
                rng=jax.random.PRNGKey(0))
            part = nemotron_ref.experts(
                {**lp, "up": lp["up"][first:first + 2],
                 "down": lp["down"][first:first + 2]}, x, SZ,
                first_expert=first, held=2)
            assert rel(blobs["y"], part) < 1e-5, first
            total = total + blobs["y"] - shared
            rows += float(jnp.sum(blobs["rows"]))
    assert rows == 4 * 2 * 32
    assert rel(total + shared, want) < 1e-5
    assert rel(total, want) > 0.1      # the shared expert is no small part


# the parent's `_sorted_rows` and `_sorted_rows_bwd`, word for word: the
# oracle the one body that now serves both forms is held to, to the bit

def _parent_sorted_rows(dot, k, act, x, w, banks, order, inv, sizes):
    w1, w3, w2 = banks
    live = moe_ops._live(order, sizes)
    xs = jnp.where(live, moe_ops._dispatch(x, order, inv, k), 0)
    a = dot(xs, w1, sizes)
    b = dot(xs, w3, sizes)
    ys = dot(act(a) * b, w2, sizes)
    y = moe_ops._combine(moe_ops._weigh(
        ys, moe_ops._permute(w, order, inv), live), order, inv, k)
    return y, (xs, a, b, ys)


def _parent_sorted_rows_bwd(k, act, w, banks, order, inv, sizes, saved, g):
    w1, w3, w2 = banks
    xs, a, b, ys = saved
    live = moe_ops._live(order, sizes)
    d_ys, d_w = jax.vjp(lambda ys, w: moe_ops._weigh(ys, w, live), ys,
                        moe_ops._permute(w, order, inv)
                        )[1](moe_ops._dispatch(g, order, inv, k))
    d_w = moe_ops._unsort(d_w, inv)
    h, gate_bwd = jax.vjp(lambda a, b: act(a) * b, a, b)
    d_h, d_w2 = moe_ops.grouped_dot_t(h, w2, sizes, d_ys)
    d_a, d_b = gate_bwd(d_h)
    d_xs1, d_w1 = moe_ops.grouped_dot_t(xs, w1, sizes, d_a)
    d_xs3, d_w3 = moe_ops.grouped_dot_t(xs, w3, sizes, d_b)
    d_x = moe_ops._combine(jnp.where(live, d_xs1 + d_xs3, 0), order, inv, k)
    return d_x, d_w, (d_w1, d_w3, d_w2)


def _tiny_expert_layers():
    import generate_models as g
    return {"smallthinker": (g.SMALLTHINKER_TINY, "relu"),
            "joyai": (g.JOYAI_TINY, "silu"), "sdar": (g.SDAR_TINY, "silu"),
            "zaya": (g.ZAYA_TINY, "silu")}


@pytest.mark.parametrize("recipe", ["smallthinker", "joyai", "sdar", "zaya"])
@pytest.mark.parametrize("side", ["forward", "backward"])
def test_the_gated_path_is_the_parent_s_to_the_bit(recipe, side):
    """At the expert sizes of the four accepted tiny recipes: the one body
    of `_sorted_rows` / `_sorted_rows_bwd`, with the gate product present,
    gives the parent's numbers bit for bit."""
    sizes, activation = _tiny_expert_layers()[recipe]
    dim, width, held, k = (sizes["dim"], sizes["expert_width"],
                           sizes["experts_held"], sizes["top_k"])
    t = 32
    keys = jax.random.split(jax.random.PRNGKey(21), 7)
    x = jax.random.normal(keys[0], (t, dim))
    w = jax.random.uniform(keys[1], (k * t,))
    banks = (0.3 * jax.random.normal(keys[2], (held, dim, width)),
             0.3 * jax.random.normal(keys[3], (held, dim, width)),
             0.3 * jax.random.normal(keys[4], (held, width, dim)))
    local = jax.random.randint(keys[5], (k * t,), 0, held + 1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes_ = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    act = moe_ops.ACTIVATIONS[activation]
    args = (k, act, x, w, banks, order, inv, sizes_)
    y, saved = moe_ops._sorted_rows(moe_ops.grouped_dot, *args)
    y0, saved0 = _parent_sorted_rows(moe_ops.grouped_dot, *args)
    if side == "forward":
        np.testing.assert_array_equal(y, y0)
        for got, want in zip(jax.tree.leaves(saved), saved0):
            np.testing.assert_array_equal(got, want)
        return
    g = jax.random.normal(keys[6], (t, dim))
    got = moe_ops._sorted_rows_bwd(k, act, w, banks, order, inv, sizes_,
                                   saved, g)
    want = _parent_sorted_rows_bwd(k, act, w, banks, order, inv, sizes_,
                                   saved0, g)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pairs,held,experts,shares,rows", [
    (49152, 16, 64, 1.5, 18432), (65536, 16, 256, 1.5, 6144),
    (131072, 16, 128, 1.5, 24576), (8192, 8, 16, 1.5, 6144),
    (49152, 8, 128, 1.5, 4608), (49152, 8, 128, 2.5, 7680),
    (49152, 8, 128, 16.0, 49152), (128, 2, 32, 2.5, 128)],
    ids=["smallthinker", "joyai", "sdar", "zaya", "default", "nemotron",
         "every_pair", "tiny"])
def test_a_share_s_buffer_holds_row_bound_shares(pairs, held, experts,
                                                 shares, rows):
    """Whole row tiles at or over `row_bound` x the held experts' share;
    the default is what the four older recipes' buffers were."""
    assert moe_ops._row_bound(pairs, held, experts, shares) == rows
    if shares == 1.5:
        assert moe_ops._row_bound(pairs, held, experts) == rows


def test_the_recipe_s_buffers_hold_two_and_a_half_shares():
    import generate_models as g
    npar = NetParameter.from_file(os.path.join(MODELS, "train_val.prototxt"))
    bounds = {l.moe_param.row_bound for l in npar.layer if l.type == "MoE"}
    assert bounds == {g.NEMOTRON_ROW_BOUND} == {2.5}
    with pytest.raises(ValueError, match="row_bound 0.5: at least 1"):
        net_from(MOE.replace("gated: false", "gated: false row_bound: 0.5")
                 % (32, 2, 0, 0.3))


@pytest.mark.parametrize("text,match", [
    ('gated: false', "gated and shared_experts need dropless: true"),
    ('activation: "relu2"', "gated and shared_experts need dropless: true"),
    ('dropless: true activation: "gelu"', "relu | silu | relu2"),
])
def test_the_expert_layer_refuses_a_form_it_does_not_have(text, match):
    from caffe_mpi_tpu.proto.netshape import analyze_net
    net = """
layer { name: "in" type: "Input" top: "x"
        input_param { shape { dim: 1 dim: 16 dim: 8 } } }
layer { name: "l" type: "MoE" bottom: "x" top: "y"
        moe_param { num_experts: 4 hidden_dim: 8 %s } }""" % text
    with pytest.raises(ValueError, match=match.replace("|", r"\|")):
        net_from(net)
    problems = analyze_net(NetParameter.from_text(net),
                           phase="TRAIN").problems
    assert any(match in p.message for p in problems), problems


# -- the whole tiny net -----------------------------------------------------------

def tiny_text(flash: bool) -> str:
    import generate_models as g
    return g.nemotron_h(**g.NEMOTRON_TINY, use_flash=flash,
                        remat=g.NEMOTRON_REMAT).to_prototxt()


@pytest.fixture(scope="module")
def case():
    net = net_from(tiny_text(False))
    params, state = net.init(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, S), 0, SZ.vocab)
    feeds = {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1)}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: nemotron_ref.loss(
            nemotron_ref.from_net(p, SZ), tokens, feeds["label"], SZ))(
                params)
        logits = nemotron_ref.forward(nemotron_ref.from_net(params, SZ),
                                      tokens, SZ)
    return {"params": params, "state": state, "feeds": feeds,
            "loss": loss, "grads": grads, "logits": logits}


@pytest.fixture(scope="module", params=[False, True], ids=["jnp", "flash"])
def whole_net(request, case):
    net = net_from(tiny_text(request.param))
    fn = lambda p: net.apply(p, case["state"], case["feeds"], train=True,
                             rng=jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        (loss, blobs), grads = jax.value_and_grad(
            lambda p: (lambda out: (out[2], out[0]))(fn(p)),
            has_aux=True)(case["params"])
    return net, blobs, loss, grads


def test_the_net_s_logits_are_the_reference_s(whole_net, case):
    assert rel(whole_net[1]["logits"], case["logits"]) < 2e-5


def test_the_net_s_loss_is_the_reference_s(whole_net, case):
    assert abs(float(whole_net[2]) / float(case["loss"]) - 1) < 1e-5
    assert 0.9 * np.log(SZ.vocab) < float(whole_net[2]) \
        < 1.3 * np.log(SZ.vocab)


def test_the_blocked_loss_is_the_loss(case):
    ref = nemotron_ref.from_net(case["params"], SZ)
    with jax.default_matmul_precision("highest"):
        blocked = nemotron_ref.loss_blocked(
            ref, case["feeds"]["tokens"], case["feeds"]["label"], SZ, 16, 24,
            time_block=8)
    assert abs(float(blocked) / float(case["loss"]) - 1) < 1e-5


GROUPS = {
    "table_and_head": lambda k: k in ("embed", "logits"),
    "mamba2": lambda k: k.endswith("/ssm"),
    "attention": lambda k: k.endswith("/attn"),
    "norms": lambda k: k.endswith("/norm") or k == "ln_f",
    "experts": lambda k: k.endswith("/moe")}


@pytest.mark.parametrize("group", GROUPS)
def test_the_net_s_gradient_is_jax_grad_of_the_reference(whole_net, case,
                                                         group):
    """Leaf by leaf; the router and the selection bias have none on either
    side (frozen, and nothing trains through the scores)."""
    net, _, _, grads = whole_net
    leaves = [(layer, blob) for layer in case["grads"]
              for blob in case["grads"][layer] if GROUPS[group](layer)]
    assert leaves
    for layer, blob in leaves:
        got, want = grads[layer][blob], case["grads"][layer][blob]
        if blob in ("gate", "select_bias"):
            np.testing.assert_array_equal(got, 0.0)
            np.testing.assert_array_equal(want, 0.0)
        else:
            assert rel(got, want) < 5e-4, (layer, blob)


def test_every_leaf_belongs_to_one_group(case):
    for layer in case["params"]:
        assert sum(keep(layer) for keep in GROUPS.values()) == 1, layer


def test_the_rows_counted_are_the_rows_routed(whole_net, case):
    """`blk<l>/moe_rows` of the pattern's E layers: every (token, choice)
    pair whose expert is held, once."""
    net, blobs, _, _ = whole_net
    ref = nemotron_ref.from_net(case["params"], SZ)
    h = jnp.take(ref["table"], case["feeds"]["tokens"], axis=0)
    seen = 0
    for l, (kind, lp) in enumerate(zip(SZ.pattern, ref["layers"])):
        if kind == "E":
            u = nemotron_ref.rms(h, lp["g"], SZ.eps)
            idx, _ = nemotron_ref.route(
                jax.nn.sigmoid(u @ lp["router"]), lp["select_bias"], SZ)
            want = [int(jnp.sum(idx == e)) for e in range(SZ.experts_held)]
            assert blobs[f"blk{l}/moe_rows"].tolist() == want
            seen += sum(want)
        else:
            assert f"blk{l}/moe_rows" not in blobs
        h = nemotron_ref.layer(kind, lp, h, SZ)
    assert seen > 0


@pytest.mark.parametrize("fault", SSM_FAULTS + [
    "squared", "gated_expert", "scaling", "renormalised", "rotary"])
def test_no_term_of_the_reference_is_dead_in_the_net(case, fault):
    """Each planted fault of the benchmark's controls moves the logits."""
    planted = nemotron_ref.forward(
        nemotron_ref.from_net(case["params"], SZ), case["feeds"]["tokens"],
        SZ, **{fault: not nemotron_ref.FAULTS[fault]})
    # (small at this size: a mixer of gaussian 0.02 products over 48
    # channels adds little to a unit-normal table's rows)
    assert not rel(planted, case["logits"]) < 1e-5


@pytest.fixture(scope="module")
def trained():
    """`caffe train`'s path: the Solver on the tiny recipe."""
    from caffe_mpi_tpu.proto import SolverParameter
    from caffe_mpi_tpu.solver import Solver
    sp = SolverParameter.from_file(os.path.join(MODELS,
                                                "tiny_solver.prototxt"))
    sp.max_iter, sp.snapshot, sp.display = 60, 0, 0
    sp.snapshot_after_train = False
    sp.random_seed = 5
    solver = Solver(sp, model_dir=ROOT)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, S), 0, SZ.vocab)
    feed = lambda it: {"tokens": tokens,
                       "label": jnp.roll(tokens, -1, axis=1)}
    try:
        before = jax.tree.map(np.asarray, solver.params)
        first = float(solver.step(1, feed))
        after_one = jax.tree.map(np.asarray, solver.params)
        for _ in range(16):
            last = float(solver.step(5, feed))
    finally:
        solver.close()
    return before, after_one, first, last


def test_the_frozen_router_stays_where_it_was(trained):
    before, after_one, _, _ = trained
    for l in SZ.of_kind("E"):
        for blob in ("gate", "select_bias"):
            np.testing.assert_array_equal(after_one[f"blk{l}/moe"][blob],
                                          before[f"blk{l}/moe"][blob])
        for blob in ("w1", "w2", "shared_w1", "shared_w2"):
            assert not np.array_equal(after_one[f"blk{l}/moe"][blob],
                                      before[f"blk{l}/moe"][blob])
    for l in SZ.of_kind("M"):
        for blob, was in before[f"blk{l}/ssm"].items():
            assert not np.array_equal(after_one[f"blk{l}/ssm"][blob],
                                      was), blob


def test_the_tiny_recipe_trains(trained):
    _, _, first, last = trained
    assert np.isfinite(last) and last < 0.8 * first


# -- the generator, the counts ----------------------------------------------------

def test_the_committed_recipes_are_what_the_generator_emits():
    import generate_models as g
    for net, solver, sizes in (
            ("train_val.prototxt", "solver.prototxt", g.NEMOTRON),
            ("tiny_train_val.prototxt", "tiny_solver.prototxt",
             g.NEMOTRON_TINY)):
        text = g.nemotron_h(**sizes, remat=g.NEMOTRON_REMAT).to_prototxt()
        assert open(os.path.join(MODELS, net)).read() == text + "\n"
        assert open(os.path.join(MODELS, solver)).read() == \
            g.nemotron_solver(net, net.split("train_val")[0] + "nemotron")


def test_the_pattern_decides_each_layer():
    import generate_models as g
    text = g.nemotron_h(**{**g.NEMOTRON_TINY, "pattern": "*ME"}
                        ).to_prototxt()
    kinds = {"Attention": "*", "Mamba2": "M", "MoE": "E"}
    npar = NetParameter.from_text(text)
    assert "".join(kinds[l.type] for l in npar.layer
                   if l.type in kinds) == "*ME"
    with pytest.raises(ValueError, match="pattern letter"):
        g.nemotron_h(**{**g.NEMOTRON_TINY, "pattern": "MX"})


@pytest.mark.parametrize("net", ["train_val.prototxt",
                                 "tiny_train_val.prototxt"])
def test_netshape_agrees_with_setup(net):
    """Every layer's tops and blobs, as the static rules infer them and as
    the layers declare them; the rules find no problem."""
    from caffe_mpi_tpu.proto.netshape import analyze_net
    path = os.path.join(MODELS, net)
    built = Net(NetParameter.from_file(path), phase="TRAIN")
    analysis = analyze_net(NetParameter.from_file(path), phase="TRAIN")
    assert not analysis.problems, analysis.problems
    for have, inferred in zip(built.layers, analysis.layers):
        assert [tuple(s) for s in have.out_shapes] == \
            [tuple(s) for s in inferred.out_shapes], have.name
        assert {n: tuple(d.shape) for n, d in have.params.items()} == \
            {n: p.shape for n, p in inferred.params.items()}, have.name


def test_the_recipe_is_the_configuration():
    """The published widths in the prototxt; the file's three cuts."""
    import generate_models as g
    sz = nemotron_ref.sizes_from_config(CONFIG)
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (sz.hidden, sz.ssm_heads, sz.ssm_head_dim, sz.ssm_state,
            sz.ssm_groups, sz.conv_kernel, sz.chunk, sz.heads, sz.kv_heads,
            sz.head_dim, sz.expert_width, sz.shared_width, sz.experts,
            sz.top_k, sz.scaling) == (
                2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 128, 6,
                2.5)
    published = CONFIG["published"]["hybrid_override_pattern"]
    assert len(published) == CONFIG["published"]["num_hidden_layers"] == 52
    assert published.startswith(sz.pattern) and "EMEMEM*" in sz.pattern * 2
    assert (sz.layers, sz.experts_held, sz.vocab) == (
        CONFIG["num_hidden_layers"], 8, 16384)
    mine = dict(vocab=sz.vocab, dim=sz.hidden, pattern=sz.pattern,
                ssm_heads=sz.ssm_heads, ssm_head_dim=sz.ssm_head_dim,
                ssm_state=sz.ssm_state, ssm_groups=sz.ssm_groups,
                conv_kernel=sz.conv_kernel, chunk=sz.chunk, heads=sz.heads,
                kv_heads=sz.kv_heads, head_dim=sz.head_dim,
                experts=sz.experts, experts_held=sz.experts_held,
                top_k=sz.top_k, expert_width=sz.expert_width,
                shared_width=sz.shared_width, scaling=sz.scaling, eps=sz.eps)
    assert {k: g.NEMOTRON[k] for k in mine} == mine
    assert g.NEMOTRON["time_step"] == tuple(
        CONFIG[f"time_step_{end}"] for end in ("min", "max", "floor"))
    assert CONFIG["sizes"]["learnable_parameters"] == \
        nemotron_ref.param_count(sz) == 666_963_456
    assert CONFIG["sizes"]["forward_macs_per_sequence_of_8192"] == \
        nemotron_ref.macs_per_sample(sz, 8192)


def test_the_counts_are_the_blobs_and_the_static_macs(case):
    """`param_count` is what `Net.init` holds; `macs_per_sample` is the
    static MAC model's sum over the net's layers (proto/netshape.py), which
    counts the same products: the Mamba-2 layer's two and its recurrence
    by the definition, whatever the chunk, an ungated expert's two."""
    from caffe_mpi_tpu.proto.netshape import analyze_net, layer_macs
    held = sum(a.size for p in case["params"].values() for a in p.values())
    assert held == nemotron_ref.param_count(SZ)
    analysis = analyze_net(NetParameter.from_text(tiny_text(False)),
                           phase="TRAIN")
    static = sum(layer_macs(info) or 0 for info in analysis.layers)
    assert static == nemotron_ref.macs_per_sample(SZ, S)


def test_the_scan_s_cost_is_the_recurrence_s_own():
    """2 H P N multiply-accumulates a position, in the roofline and in
    `macs_per_sample` (what `mfu` reads) alike: neither moves with the
    chunk."""
    sz = nemotron_ref.sizes_from_config(CONFIG)
    flops, nbytes = nemotron_ref.scan_cost(sz, 1, 8192, backward=False)
    assert flops == 2 * 2 * 64 * 64 * 128 * 8192
    assert nbytes == 2 * (2 * 4096 + 2 * 1024 + 64) * 8192
    assert 2 * nemotron_ref.scan_macs_per_token(sz) * 8192 == flops
    wider = dataclasses.replace(sz, chunk=256)
    assert nemotron_ref.macs_per_sample(wider, 8192) == \
        nemotron_ref.macs_per_sample(sz, 8192)
    assert nemotron_ref.scan_cost(sz, 1, 8192) == (3 * flops, 3 * nbytes)
