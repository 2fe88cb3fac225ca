"""Xing4.0-29B-A4B through prototxt -> Net, at a tiny size on the CPU,
against the benchmark's plain reference (benchmarks/reference/xing_ref.py):
four residual streams of D 64 mixed by the hyper-connection layers around
every sub-layer, latent attention of 4 heads (16 + 8 rotary query/key lanes
under the published YaRN, 16 value lanes) over ranks 48 / 32, S 32, a dense
block of width 96 and two expert blocks of 8 experts (2 held, 4 a token,
width 32, one shared), vocabulary 64 — the sizes of
`models/xing4_29b_a4b/tiny_train_val.prototxt`, which the same generator
emits as the benchmark's recipe.

The reference has no analogue: the reference framework's residual is an
Eltwise SUM (src/caffe/layers/eltwise_layer.cpp), and it has neither
attention nor experts (SURVEY §5.7, §2.7).
"""

import dataclasses
import json
import math
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

from reference import joyai_ref, xing_ref  # noqa: E402

from caffe_mpi_tpu.layers.hyper_connection import sinkhorn  # noqa: E402
from caffe_mpi_tpu.net import Net  # noqa: E402
from caffe_mpi_tpu.ops.attention import (Yarn, attention,  # noqa: E402
                                         rope_tables, yarn_inv_freq)
from caffe_mpi_tpu.proto import NetParameter  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "xing4_29b_a4b.json")))
SZ = xing_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY = os.path.join(ROOT, "models", "xing4_29b_a4b",
                    "tiny_train_val.prototxt")
PUBLISHED_YARN = Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def net_from(text: str, batch: int = 2, precision: str = "f32") -> Net:
    npar = NetParameter.from_text(text)
    for lp in npar.layer:
        if lp.type == "Input":
            for shape in lp.input_param.shape:
                shape.dim[0] = batch
    return Net(npar, phase="TRAIN", precision=precision)


def tokens(batch=2, seq=32, seed=1) -> dict:
    t = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                           SZ.vocab)
    return {"tokens": t, "label": jnp.roll(t, -1, 1)}


# -- Sinkhorn-Knopp -------------------------------------------------------------

class TestSinkhorn:
    @pytest.mark.parametrize("spread,iters,rows_within", [
        (0.5, 20, 1e-5), (2 ** 0.5, 20, 0.03), (2 ** 0.5, 200, 1e-5)],
        ids=["narrow", "recipe_20", "recipe_200"])
    def test_doubly_stochastic(self, spread, iters, rows_within):
        """Columns are divided last, so they sum to 1 after any number of
        iterations; rows converge: within 1e-5 at 20 iterations for logits
        of spread 0.5, within 0.03 for the worst of 4,096 tokens at the
        recipe's starting spread (a unit-variance product plus a unit bias)
        and 20 iterations, where a few matrices with entries e^-4 apart
        converge slowly, and within 1e-5 by 200."""
        logits = jax.random.normal(jax.random.PRNGKey(0), (4096, 4, 4))
        m = np.asarray(sinkhorn(jnp.exp(logits * spread), iters, 1e-6))
        assert np.allclose(m.sum(-2), 1.0, atol=1e-5)
        assert np.allclose(m.sum(-1), 1.0, atol=rows_within)
        assert (m >= 0).all()
        # and it is no identity, nor symmetric
        assert np.abs(m - np.swapaxes(m, -1, -2)).max() > 0.05

    def test_the_layer_s_layout_is_the_reference_s(self):
        """The layer iterates with the tokens last, [row, column, token];
        the reference over (..., row, column): the same matrices."""
        m = jnp.exp(jax.random.normal(jax.random.PRNGKey(1), (16, 4, 4)))
        ours = sinkhorn(jnp.moveaxis(m, 0, -1), 20, 1e-6, axes=(0, 1))
        want = xing_ref.sinkhorn(m, 20, 1e-6)
        assert rel(jnp.moveaxis(ours, -1, 0), want) < 1e-6

    @pytest.mark.parametrize("sign", [1.0, -1.0], ids=["max", "min"])
    def test_the_clamp_is_reached_at_30(self, sign):
        """A logit past the clamp gives what the clamp itself gives, one
        inside it does not."""
        net = net_from(HC_NET % 4)
        params, _ = net.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 4, 16))
        coef = lambda b: net.apply(
            {**params, "hc": {**params["hc"], "bias": b}}, {}, {"x": x},
            train=False)[0]["coef"]
        bias = params["hc"]["bias"]
        at = lambda v: coef(bias.at[8:].set(sign * v)
                            .at[8].set(0.0))   # one entry that stays small
        ns = lambda v: np.asarray(at(v))
        assert np.array_equal(ns(300.0), ns(1e4))
        assert not np.array_equal(ns(20.0), ns(300.0))
        # what the reference computes with its clamp at the same logits
        hp = {"phi": params["hc"]["phi"].T, "alpha": params["hc"]["alpha"],
              "bias": bias.at[8:].set(sign * 300.0).at[8].set(0.0)}
        sz = dataclasses.replace(SZ, hidden=16)
        with jax.default_matmul_precision("highest"):
            _, h_post, h_res = xing_ref.coefficients(hp, x, sz)
        assert rel(ns(300.0), jnp.concatenate(
            [h_post, h_res.reshape(2, 8, 16)], -1)) < 1e-5


# -- the hyper-connection layers ---------------------------------------------------

HC_NET = """
    layer { name: "in" type: "Input" top: "x"
            input_param { shape { dim: 2 dim: 8 dim: %d dim: 16 } } }
    layer { name: "hc" type: "HyperConnectionPre" bottom: "x" top: "h"
            top: "coef" hyper_connection_param {
              bias_filler { type: "gaussian" std: 1.0 } } }"""


class TestHyperConnection:
    def test_pre_against_the_reference(self):
        net = net_from(HC_NET % 4)
        params, _ = net.init(jax.random.PRNGKey(3))
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 4, 16))
        blobs, _, _ = net.apply(params, {}, {"x": x}, train=False)
        p = params["hc"]
        assert p["phi"].shape == (24, 64) and p["alpha"].shape == (3,)
        hp = {"phi": p["phi"].T, "bias": p["bias"], "alpha": p["alpha"]}
        sz = dataclasses.replace(SZ, hidden=16)
        with jax.default_matmul_precision("highest"):
            h_pre, h_post, h_res = xing_ref.coefficients(hp, x, sz)
        assert rel(blobs["h"], jnp.einsum("nsi,nsic->nsc", h_pre, x)) < 1e-5
        assert rel(blobs["coef"][..., :4], h_post) < 1e-5
        assert rel(blobs["coef"][..., 4:], h_res.reshape(2, 8, 16)) < 1e-5
        # the mix depends on the token, and H_post is no constant
        assert float(jnp.std(h_res[..., 0, 1])) > 0.01
        assert float(jnp.std(h_post)) > 0.05

    def test_post_is_h_res_x_plus_h_post_f(self):
        net = net_from(HC_NET % 4 + """
            layer { name: "f" type: "Parameter" top: "y"
                    parameter_param { shape { dim: 2 dim: 8 dim: 16 } } }
            layer { name: "post" type: "HyperConnectionPost" bottom: "x"
                    bottom: "y" bottom: "coef" top: "out"
                    hyper_connection_param { } }""")
        params, _ = net.init(jax.random.PRNGKey(5))
        params["f"]["weight"] = jax.random.normal(jax.random.PRNGKey(6),
                                                  (2, 8, 16))
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 4, 16))
        blobs, _, _ = net.apply(params, {}, {"x": x}, train=False)
        h_post = np.asarray(blobs["coef"][..., :4], np.float64)
        h_res = np.asarray(blobs["coef"][..., 4:], np.float64).reshape(
            2, 8, 4, 4)
        xs, y = np.asarray(x, np.float64), np.asarray(blobs["y"], np.float64)
        want = np.zeros_like(xs)
        for i in range(4):          # stream i, written out
            want[:, :, i] = sum(h_res[:, :, i, j, None] * xs[:, :, j]
                                for j in range(4)) + h_post[:, :, i, None] * y
        assert rel(blobs["out"], want) < 1e-6

    @pytest.mark.parametrize("streams", [3, 5])
    def test_a_stream_of_another_width_is_refused(self, streams):
        with pytest.raises(ValueError, match="expects a stream"):
            net_from(HC_NET % streams)


# -- the hand-written backward passes ------------------------------------------------

def _hc_layers(c: int, precision: str = "f32"):
    """The `HyperConnectionPre` and `HyperConnectionPost` of a stream
    (2, 8, 4, c), built by `Net` from a prototxt."""
    net = net_from(f"""
        layer {{ name: "in" type: "Input" top: "x"
                input_param {{ shape {{ dim: 2 dim: 8 dim: 4 dim: {c} }} }} }}
        layer {{ name: "hc" type: "HyperConnectionPre" bottom: "x" top: "h"
                top: "coef" hyper_connection_param {{ }} }}
        layer {{ name: "post" type: "HyperConnectionPost" bottom: "x"
                bottom: "h" bottom: "coef" top: "out"
                hyper_connection_param {{ }} }}""", precision=precision)
    return net.layers[1], net.layers[2]


def _as_net_applies(layer, remat: bool):
    """fn(params, *bottoms) -> tops, under `jax.checkpoint` keeping the
    layer's `kept_under_remat`, as `Net.apply_range` applies a layer with
    `remat: true`."""
    fn = lambda p, *xs: tuple(layer.apply(p, {}, list(xs), train=True,
                                          rng=None)[0])
    if remat:
        fn = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                *layer.kept_under_remat))
    return fn


def _pre_by_the_equations(p, x, c):
    """(H_pre x, [H_post | H_res]) by `xing_ref.coefficients`, float32."""
    sz = dataclasses.replace(SZ, hidden=c)
    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = xing_ref.coefficients(
            {"phi": p["phi"].T, "bias": p["bias"], "alpha": p["alpha"]},
            x, sz)
        mixed = jnp.einsum("nsi,nsic->nsc", h_pre, x)
    return mixed, jnp.concatenate([h_post, h_res.reshape(2, 8, 16)], -1)


def _post_by_the_equations(x, y, coef):
    h_res = coef[..., 4:].reshape(2, 8, 4, 4)
    with jax.default_matmul_precision("highest"):
        return (jnp.einsum("nsij,nsjc->nsic", h_res, x)
                + coef[..., :4, None] * y[:, :, None],)


def _hc_inputs(c: int):
    ks = jax.random.split(jax.random.PRNGKey(c), 9)
    x = jax.random.normal(ks[0], (2, 8, 4, c))
    p = {"phi": jax.random.normal(ks[1], (24, 4 * c)) * (4 * c) ** -0.5,
         "bias": jax.random.normal(ks[2], (24,)),
         "alpha": 1.0 + 0.3 * jax.random.normal(ks[3], (3,))}
    y = jax.random.normal(ks[4], (2, 8, c))
    coef = jnp.concatenate([
        2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (2, 8, 4))),
        jax.nn.softmax(jax.random.normal(ks[6], (2, 8, 4, 4)), -1)
        .reshape(2, 8, 16)], -1)
    cts = {"mixed": jax.random.normal(ks[7], (2, 8, c)),
           "coef": jax.random.normal(ks[8], (2, 8, 20)),
           "out": jax.random.normal(ks[8], (2, 8, 4, c))}
    return x, p, y, coef, cts


# (what receives a cotangent, the gradient compared): every gradient of
# each layer, with the Pre layer's two tops' cotangents each alone
_HC_GRADS = [("pre", top, leaf) for top in ("mixed", "coef")
             for leaf in ("x", "phi", "bias", "alpha")] \
    + [("post", "out", leaf) for leaf in ("x", "y", "coef")]


@pytest.fixture(scope="module", params=[(16, False), (16, True),
                                        (128, False), (128, True),
                                        (200, True)],
                ids=["c16", "c16_remat", "c128", "c128_remat", "c200_remat"])
def hc_grads(request):
    """Each layer's gradients through its custom VJP (alone, or under the
    checkpoint `Net` puts around a `remat: true` layer) and through
    `jax.vjp` of the equations, float32. C 16 and 200 are no multiple of
    128: the streams' lane slices are not tile-aligned there."""
    c, remat = request.param
    x, p, y, coef, cts = _hc_inputs(c)
    pre, post = _hc_layers(c)
    zero = {k: jnp.zeros_like(v) for k, v in cts.items()}
    got, want = {}, {}
    for top in ("mixed", "coef"):
        ct = (cts["mixed"] if top == "mixed" else zero["mixed"],
              cts["coef"] if top == "coef" else zero["coef"])
        _, vjp = jax.vjp(_as_net_applies(pre, remat), p, x)
        gp, gx = vjp(ct)
        got["pre", top] = {"x": gx, **gp}
        _, vjp = jax.vjp(lambda p, x: _pre_by_the_equations(p, x, c), p, x)
        gp, gx = vjp(ct)
        want["pre", top] = {"x": gx, **gp}
    _, vjp = jax.vjp(_as_net_applies(post, remat), {}, x, y, coef)
    got["post", "out"] = dict(zip(("x", "y", "coef"),
                                  vjp((cts["out"],))[1:]))
    _, vjp = jax.vjp(_post_by_the_equations, x, y, coef)
    want["post", "out"] = dict(zip(("x", "y", "coef"), vjp((cts["out"],))))
    return got, want


class TestHandWrittenBackward:
    @pytest.mark.parametrize("layer,top,leaf", _HC_GRADS,
                             ids=["-".join(g) for g in _HC_GRADS])
    def test_each_gradient_is_the_equations(self, hc_grads, layer, top,
                                            leaf):
        got, want = hc_grads
        g, w = got[layer, top][leaf], want[layer, top][leaf]
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.linalg.norm(w)) > 0.0
        assert rel(g, w) < 1e-5

    def test_remat_keeps_the_logits_and_the_norm(self, capsys):
        """Under `remat: true` the Pre layer's backward pass reads the
        kept product and norm factor, 2 n + n^2 + 1 floats a token, and
        does not read the stream for them again."""
        from jax.ad_checkpoint import print_saved_residuals
        pre, _ = _hc_layers(16)
        x, p, *_ = _hc_inputs(16)
        print_saved_residuals(_as_net_applies(pre, True), p, x)
        kept = [line.split()[0] for line in
                capsys.readouterr().out.splitlines()
                if line and "from the argument" not in line
                and "from a constant" not in line]
        assert sorted(kept) == ["f32[16]", "f32[24,16]"]

    def test_bf16_is_near_the_equations_in_float32(self):
        """The layers in bf16 (the stream, F and the product's operands;
        the coefficients float32) against the float32 equations on the
        same rounded inputs: every gradient within 0.5 % of its norm, and
        alpha's, three sums over the tokens that partly cancel, within 2 %.
        Read: 0.16-0.28 %, alpha 1.35 %; jax's own backward pass of the
        same equations in bf16 reads the same, the stream's 0.32 %."""
        c = 128
        x, p, y, coef, cts = _hc_inputs(c)
        bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        x, y, p = bf(x), bf(y), {**p, "phi": bf(p["phi"])}
        pre, post = _hc_layers(c, precision="bf16")
        ct = (cts["mixed"].astype(jnp.bfloat16), cts["coef"])
        (mixed, _), vjp = jax.vjp(_as_net_applies(pre, True), p,
                                  x.astype(jnp.bfloat16))
        assert mixed.dtype == jnp.bfloat16
        gp, gx = vjp(ct)
        assert gx.dtype == jnp.bfloat16
        _, vjp = jax.vjp(lambda p, x: _pre_by_the_equations(p, x, c), p, x)
        wp, wx = vjp((cts["mixed"], cts["coef"]))
        assert rel(gx, wx) < 0.005
        for k in wp:
            assert rel(gp[k], wp[k]) < (0.02 if k == "alpha" else 0.005), k
        (out,), vjp = jax.vjp(_as_net_applies(post, True), {},
                              x.astype(jnp.bfloat16),
                              y.astype(jnp.bfloat16), coef)
        assert out.dtype == jnp.bfloat16
        got = vjp((cts["out"].astype(jnp.bfloat16),))[1:]
        _, vjp = jax.vjp(_post_by_the_equations, x, y, coef)
        for g, w in zip(got, vjp((cts["out"],))):
            assert rel(g, w) < 0.005


# -- YaRN -------------------------------------------------------------------------------

def yarn_by_hand(d, theta, factor, original, fast, slow):
    """DeepSeek-V3's yarn_find_correction_range / yarn_linear_ramp_mask,
    written out in float64."""
    dim = lambda turns: d * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(dim(fast)), 0)
    high = min(math.ceil(dim(slow)), d - 1)
    out = []
    for i in range(d // 2):
        extra = 1.0 / theta ** (2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        mask = 1.0 - ramp
        out.append(extra / factor * (1 - mask) + extra * mask)
    return np.array(out), low, high


class TestYarn:
    def test_inv_freq_is_the_written_out_formula(self):
        want, low, high = yarn_by_hand(64, 1e4, 64.0, 4096, 32.0, 1.0)
        assert (low, high) == (10, 23)
        got = np.asarray(yarn_inv_freq(64, 1e4, PUBLISHED_YARN))
        assert np.allclose(got, want, rtol=1e-6)
        plain = 1e4 ** (-np.arange(32) / 32)
        assert np.allclose(got[:10], plain[:10], rtol=1e-6)
        assert np.allclose(got[23:], plain[23:] / 64, rtol=1e-6)
        assert np.allclose(np.asarray(xing_ref.inv_freq(dataclasses.replace(
            SZ, rot=64))), want, rtol=1e-6)

    def test_the_scale_factors(self):
        assert PUBLISHED_YARN.cos_sin_factor == 1.0
        assert abs(PUBLISHED_YARN.score_factor - 2.00474) < 1e-5
        assert abs(PUBLISHED_YARN.score_factor
                   - (0.1 * math.log(64) + 1) ** 2) < 1e-12
        assert Yarn(64.0, 4096, mscale=1.0).score_factor == 1.0

    @pytest.mark.parametrize("pairs", [False, True], ids=["halves", "pairs"])
    def test_without_rope_scaling_the_tables_are_bit_for_bit_as_they_were(
            self, pairs):
        def as_they_were(s, d, theta):
            half = d // 2
            ang = (jnp.arange(s).astype(jnp.float32)[:, None]
                   * theta ** (-jnp.arange(half, dtype=jnp.float32)
                               / half)[None, :])
            both = ((lambda t: jnp.repeat(t, 2, axis=-1)) if pairs
                    else (lambda t: jnp.concatenate([t, t], -1)))
            return both(jnp.cos(ang)), both(jnp.sin(ang))
        for s, d, theta in ((32, 8, 1e4), (300, 64, 3.2e7)):
            cos, sin = rope_tables(s, d, theta, pairs=pairs)
            cos0, sin0 = as_they_were(s, d, theta)
            assert np.array_equal(np.asarray(cos), np.asarray(cos0))
            assert np.array_equal(np.abs(np.asarray(sin)),
                                  np.abs(np.asarray(sin0)))

    def test_the_tables_turn_by_yarn_s_frequencies(self):
        cos, _ = rope_tables(12, 64, 1e4, pairs=True, yarn=PUBLISHED_YARN)
        want = np.cos(np.arange(12)[:, None] * yarn_by_hand(
            64, 1e4, 64.0, 4096, 32.0, 1.0)[0][None, :])
        assert np.allclose(np.asarray(cos)[:, ::2], want, atol=1e-5)

    def test_yarn_is_latent_attention_s_alone(self):
        with pytest.raises(ValueError, match="latent attention's alone"):
            net_from("""
                layer { name: "in" type: "Input" top: "x"
                        input_param { shape { dim: 1 dim: 8 dim: 16 } } }
                layer { name: "l" type: "Attention" bottom: "x" top: "y"
                        attention_param { num_heads: 2 rope_theta: 10.0
                            rope_scaling_factor: 4.0
                            rope_original_max_position: 8 } }""")


# -- the flash kernels with a scale of their own -----------------------------------

@pytest.fixture(scope="module", params=[128, 200], ids=["one_tile", "pads"])
def scaled_pair(request):
    """(jnp, flash): out and the three gradients at widths 192 / 128 and
    YaRN's score scale, the second through the interpreted kernels."""
    s = request.param
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k = (jax.random.normal(key, (1, s, 2, 192)) for key in ks[:2])
    v, do = (jax.random.normal(key, (1, s, 2, 128)) for key in ks[2:])
    scale = PUBLISHED_YARN.score_factor / math.sqrt(192)

    def run(flash):
        out, vjp = jax.vjp(lambda q, k, v: attention(
            q, k, v, causal=True, use_flash=flash, scale=scale), q, k, v)
        return (out, *vjp(do))
    return run(False), run(True)


@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_flash_with_a_scale_is_the_jnp_path(scaled_pair, which):
    want, got = scaled_pair
    assert got[which].shape == want[which].shape
    assert rel(got[which], want[which]) < 2e-6


def test_the_scale_is_applied():
    """A key equal to the query and one orthogonal to it: softmax weights
    one can write down, at twice 1 / sqrt(192)."""
    q = jnp.zeros((1, 2, 1, 192)).at[0, :, 0, 0].set(3.0)
    k = jnp.zeros((1, 2, 1, 192)).at[0, 0, 0, 0].set(3.0)
    v = jnp.zeros((1, 2, 1, 128)).at[0, 0, 0, 0].set(1.0)
    for flash in (False, True):
        out = attention(q, k, v, causal=True, use_flash=flash,
                        scale=2.0 / math.sqrt(192.0))
        a = np.exp(18.0 / np.sqrt(192.0))
        assert abs(float(out[0, 1, 0, 0]) - a / (a + 1.0)) < 1e-6


# -- the expert layer's share, inside a hyper-connection ---------------------------

SHARE_NET = HC_NET % 4 + """
    layer { name: "ln" type: "RMSNorm" bottom: "h" top: "m" }
    layer { name: "moe" type: "MoE" bottom: "m" bottom: "m" top: "y"
            top: "rows" moe_param { num_experts: 8 hidden_dim: 32 top_k: 4
            dropless: true experts_held: %d first_expert: %d
            scoring: "sigmoid" routed_scaling_factor: 2.0
            activation: "silu" shared_experts: 1
            weight_filler { type: "gaussian" std: 0.2 }
            bias_filler { type: "gaussian" mean: -0.8 std: 0.05 } } }
    layer { name: "post" type: "HyperConnectionPost" bottom: "x"
            bottom: "y" bottom: "coef" top: "out"
            hyper_connection_param { } }"""


def test_four_shares_add_up_to_the_uncut_layer():
    """THE share test: what the 4 shares of 2 experts give through the
    hyper-connection, with what every chip computes alike (H_res x and the
    shared expert through H_post) counted once, is the uncut reference's
    whole sub-layer."""
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, 4, 16))
    whole = net_from(SHARE_NET % (8, 0))
    params, _ = whole.init(jax.random.PRNGKey(9))
    p, hc = params["moe"], params["hc"]
    hp = {"phi": hc["phi"].T, "bias": hc["bias"], "alpha": hc["alpha"]}
    sz = dataclasses.replace(SZ, hidden=16, experts=8, experts_held=8,
                             expert_width=32)
    lp = {"router": p["gate"], "bias": p["select_bias"], "gate": p["w1"],
          "up": p["w3"], "down": p["w2"], "s_gate": p["shared_w1"],
          "s_up": p["shared_w3"], "s_down": p["shared_w2"]}
    g = params["ln"]["scale"]
    with jax.default_matmul_precision("highest"):
        want = xing_ref.hyper(hp, x, lambda h: joyai_ref.feed_forward(
            lp, xing_ref.rms(h, g, 1e-6), sz, None), sz)
        common = xing_ref.hyper(hp, x, lambda h: joyai_ref.gated(
            xing_ref.rms(h, g, 1e-6), lp["s_gate"], lp["s_up"],
            lp["s_down"], None), sz)
    total, rows = common, 0.0
    for share in range(4):
        mine = {**params, "moe": {**p, **{k: p[k][2 * share:2 * share + 2]
                                          for k in ("w1", "w2", "w3")}}}
        blobs, _, _ = net_from(SHARE_NET % (2, 2 * share)).apply(
            mine, {}, {"x": x}, train=False)
        total = total + (blobs["out"] - common)
        rows += float(jnp.sum(blobs["rows"]))
    assert rows == 2 * 8 * 4            # every (token, choice) pair once
    assert rel(total, want) < 1e-5
    assert rel(common, want) > 0.01     # the routed experts are not nothing


# -- the whole net ---------------------------------------------------------------------

@pytest.fixture(scope="module", params=[False, True], ids=["jnp", "flash"])
def whole_net(request):
    """The tiny recipe's logits, loss and gradient in float32, and the
    reference's, on seeded weights; the flash path through the interpreted
    kernels."""
    text = open(TINY).read()
    assert "use_flash: true" in text
    if not request.param:
        text = text.replace("use_flash: true", "use_flash: false")
    net = net_from(text)
    params, state = net.init(jax.random.PRNGKey(3))
    feeds = tokens()

    def run(p):
        blobs, _, loss = net.apply(p, state, feeds, train=True,
                                   rng=jax.random.PRNGKey(0))
        return loss, blobs
    (loss, blobs), grads = jax.value_and_grad(run, has_aux=True)(params)

    def reference(p):
        return xing_ref.loss(xing_ref.from_net(p, SZ), feeds["tokens"],
                             feeds["label"], SZ)
    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.value_and_grad(reference)(params)
    want_logits = xing_ref.forward(xing_ref.from_net(params, SZ),
                                   feeds["tokens"], SZ)
    return {"net": net, "params": params, "feeds": feeds, "text": text,
            "loss": (loss, want_loss), "grads": (grads, want_grads),
            "logits": (blobs["logits"], want_logits)}


class TestWholeNet:
    @pytest.mark.parametrize("what", ["logits", "loss"])
    def test_against_the_reference(self, whole_net, what):
        got, want = whole_net[what]
        assert rel(got, want) < 1e-4

    def test_every_leaf_s_gradient_is_the_reference_s(self, whole_net):
        got, want = whole_net["grads"]
        frozen = {(l, b) for l, b, d in
                  whole_net["net"].learnable_param_decls()
                  if d.lr_mult == 0.0}
        assert frozen == {(f"{b}/moe", k) for b in ("blk1", "blk2")
                          for k in ("gate", "select_bias")}
        checked = 0
        for layer, blobs in want.items():
            if layer.endswith(("/hc1", "/hc2")):
                # a hyper-connection's bias and alpha receive sums over the
                # tokens of terms that nearly cancel (streams that start as
                # copies of one another: a change of H_res that keeps its
                # rows' sums moves little), a few 1e-6 against the
                # float32 rounding of the terms; the layer's blobs are held
                # together, and each to its own norm ten times looser
                flat = lambda tree: np.concatenate([
                    np.asarray(tree[b], np.float64).ravel() for b in blobs])
                assert rel(flat(got[layer]), flat(blobs)) < 1e-4, layer
                for blob, g in blobs.items():
                    assert rel(got[layer][blob], g) < 1e-3, (layer, blob)
                    checked += 1
                continue
            for blob, g in blobs.items():
                if (layer, blob) in frozen:
                    assert not np.any(np.asarray(got[layer][blob]))
                    continue
                assert rel(got[layer][blob], g) < 1e-4, (layer, blob)
                checked += 1
        assert checked == 63

    def test_the_stream_mix_reaches_the_loss(self, whole_net):
        """Each planted fault of the mix and of YaRN moves the reference's
        loss once the sub-layers write to the streams as much as the
        embedding is (at the published widths a feed-forward of 0.02
        weights does: its output is sqrt(9,216) 0.02 ~ 2 against the
        table's 1; at D 64 it takes weights of 0.3): the hyper-connection
        fillers make a transposed, halved, uniform or dropped mix tell."""
        net = net_from(whole_net["text"].replace("std: 0.02", "std: 0.3"))
        params, _ = net.init(jax.random.PRNGKey(3))
        feeds = whole_net["feeds"]
        ref = xing_ref.from_net(params, SZ)
        loss = lambda **how: float(xing_ref.cross_entropy(
            xing_ref.logits_block(ref, xing_ref.hidden(
                ref, feeds["tokens"], SZ, **how), 0, SZ.vocab),
            feeds["label"]))
        sound = loss()
        # the smallest, transposed H_res, moves it by 1.3e-4 of itself:
        # streams that start as copies differ only by what the sub-layers
        # wrote, and the final sum over them sees no mix that keeps the
        # columns' sums. Planted as numbers 0 the faults change nothing
        assert loss(fault={k: 0 for k in xing_ref.FAULTS}) == sound
        for name in xing_ref.FAULTS:
            assert abs(loss(fault={name: 1}) - sound) > 2e-5 * sound, name
        # one stream planted as data is the plain residual x + F(x)
        x = jnp.take(ref["embed"], feeds["tokens"], axis=0)
        with jax.default_matmul_precision("highest"):
            for lp in ref["layers"]:
                x = x + xing_ref.attention(lp, xing_ref.rms(
                    x, lp["g1"], SZ.eps), SZ, None, None)
                x = x + xing_ref.feed_forward(lp, xing_ref.rms(
                    x, lp["g2"], SZ.eps), SZ, None)
        assert rel(xing_ref.hidden(ref, feeds["tokens"], SZ,
                                   fault={"one_stream": 1}),
                   xing_ref.rms(x, ref["g_f"], SZ.eps)) < 1e-4

    def test_every_sub_layer_goes_through_a_hyper_connection(self,
                                                            whole_net):
        net = whole_net["net"]
        kinds = [layer.lp.type for layer in net.layers]
        assert kinds.count("HyperConnectionPre") == 2 * SZ.layers
        assert kinds.count("HyperConnectionPost") == 2 * SZ.layers
        # no residual sum is left but the streams' final one (and the
        # dense block's gate product)
        assert {layer.name for layer in net.layers
                if layer.lp.type == "Eltwise"} == {"blk0/act", "merge/sum"}
        remat = {layer.name for layer in net.layers if layer.lp.remat}
        assert remat == {f"blk{l}/{part}" for l in range(SZ.layers)
                         for part in ("attn", "hc1", "hc2", "hc1_out",
                                      "hc2_out")}


def test_bf16_is_near_the_reference():
    """The recipe in bf16 (the stream, the products; the coefficients and
    Sinkhorn stay float32) against the float32 reference."""
    net = net_from(open(TINY).read(), precision="bf16")
    params, state = net.init(jax.random.PRNGKey(3))
    feeds = tokens()
    blobs, _, _ = net.apply(params, state, feeds, train=True,
                            rng=jax.random.PRNGKey(0))
    assert blobs["blk0/hc1_coef"].dtype == jnp.float32
    assert blobs["blk0/hc1_out"].dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = xing_ref.forward(xing_ref.from_net(params, SZ),
                                feeds["tokens"], SZ)
    assert rel(blobs["logits"], want) < 0.05
