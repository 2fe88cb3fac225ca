"""SmallThinker-21BA3B-Instruct through prototxt -> Net, at a tiny size on
the CPU, against the benchmark's plain reference (benchmarks/reference/
lm_ref.py): D 64, 4 / 2 heads of 16, window 8, S 32, 8 experts of width
32 with 2 a token, vocabulary 64, 4 layers [0,1,1,1] — the sizes of
`models/smallthinker_21b_a3b/tiny_train_val.prototxt`, which the same
generator emits as the benchmark's recipe.

The reference has no analogue: the reference framework (a CNN-era Caffe)
has neither attention nor experts (SURVEY §5.7, §2.7).
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

from reference import lm_ref  # noqa: E402

from caffe_mpi_tpu.net import Net  # noqa: E402
from caffe_mpi_tpu.ops import moe as moe_ops  # noqa: E402
from caffe_mpi_tpu.ops.attention import attention, rope  # noqa: E402
from caffe_mpi_tpu.proto import NetParameter  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "smallthinker_21b_a3b.json")))
SZ = lm_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY = os.path.join(ROOT, "models", "smallthinker_21b_a3b",
                    "tiny_train_val.prototxt")


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def tree_rel(got, want) -> float:
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(g, w))
    return (num / sum(float(jnp.sum(b ** 2)) for b in w)) ** 0.5


def net_from(text: str, precision: str = "f32", batch: int = 2) -> Net:
    npar = NetParameter.from_text(text)
    for lp in npar.layer:
        if lp.type == "Input":
            for shape in lp.input_param.shape:
                shape.dim[0] = batch
    return Net(npar, phase="TRAIN", precision=precision)


def one_layer(body: str, bottoms="x", shape=(2, 32, 64)) -> Net:
    dims = " ".join(f"dim: {d}" for d in shape)
    return net_from(f"""
        layer {{ name: "in" type: "Input" top: "x"
                 input_param {{ shape {{ {dims} }} }} }}
        layer {{ name: "l" bottom: "{bottoms}" top: "y" {body} }}""")


def run_layer(net: Net, x, seed=0):
    params, state = net.init(jax.random.PRNGKey(seed))
    blobs, _, _ = net.apply(params, state, {"x": x}, train=True,
                            rng=jax.random.PRNGKey(0))
    return params["l"], blobs


X = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64), jnp.float32)


class TestLayers:
    @pytest.mark.parametrize("eps", [1e-6, 1e-2])
    def test_rms_norm(self, eps):
        net = one_layer(f'type: "RMSNorm" rms_norm_param {{ eps: {eps} }}')
        params, state = net.init(jax.random.PRNGKey(0))
        g = jax.random.normal(jax.random.PRNGKey(1), (64,)) + 1.0
        params["l"]["scale"] = g
        blobs, _, _ = net.apply(params, state, {"x": 3.0 * X}, train=True,
                                rng=None)
        assert rel(blobs["y"], lm_ref.rms(3.0 * X, g, eps)) < 1e-6
        # no mean is subtracted: a constant row keeps its sign and size
        ones = jnp.ones((2, 32, 64))
        blobs, _, _ = net.apply(params, state, {"x": ones}, train=True,
                                rng=None)
        assert rel(blobs["y"], jnp.broadcast_to(g, ones.shape)
                   / np.sqrt(1 + eps)) < 1e-6

    @pytest.mark.parametrize("rotary", [0, 1], ids=["nope", "rope"])
    @pytest.mark.parametrize("windowed", [0, 1], ids=["global", "window"])
    @pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
    def test_attention_layer_against_reference(self, rotary, windowed,
                                               flash):
        """Grouped heads (4 over 2), rotary on/off, window on/off, through
        the layer, both attention paths, against equations 3-4."""
        net = one_layer(f"""type: "Attention" attention_param {{
            num_heads: 4 num_kv_heads: 2 head_dim: 16 causal: true
            bias_term: false use_flash: {str(flash).lower()}
            window: {8 * windowed} rope_theta: {1.5e6 * rotary}
            weight_filler {{ type: "gaussian" std: 0.2 }} }}""")
        lp, blobs = run_layer(net, X)
        sz = dataclasses.replace(SZ, window_layout=(windowed,),
                                 rope_layout=(rotary,))
        qkv = lp["qkv_weight"]
        ref = {"wq": qkv[:64].T, "wk": qkv[64:96].T, "wv": qkv[96:].T,
               "wo": lp["proj_weight"].T}
        with jax.default_matmul_precision("highest"):
            want = lm_ref.attention(ref, X, sz, 0, 16, None)
        assert rel(blobs["y"], want) < 2e-6

    def test_rope_is_a_rotation_by_position(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 2, 16))
        y = rope(x, 1.5e6)
        np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # angle 0
        np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                                   jnp.linalg.norm(x, axis=-1), rtol=1e-5)
        assert rel(y, lm_ref.rotate(x, 1.5e6)) < 1e-6
        # scores depend on the distance alone: shift both by one position
        q, k = rope(x, 1e4), rope(x[:, ::-1], 1e4)
        same = jnp.broadcast_to(x[:, :1], x.shape)
        rq = rope(same, 1e4)
        d01 = jnp.sum(rq[0, 0, 0] * rq[0, 1, 0])
        d12 = jnp.sum(rq[0, 1, 0] * rq[0, 2, 0])
        np.testing.assert_allclose(d01, d12, rtol=1e-5)
        assert q.shape == k.shape

    @pytest.mark.parametrize("heads,kv,window,seq", [
        (4, 2, 8, 32), (4, 2, 0, 32), (4, 1, 130, 384), (6, 2, 200, 300),
        (2, 2, 70, 256),
        # every class of tile (window edge, inside, diagonal, padded tail)
        # and every empty-run corner of _tile_runs: five tiles of 128 ...
        (4, 2, 0, 640), (4, 2, 200, 640), (4, 2, 256, 640), (4, 2, 128, 640),
        (4, 2, 1000, 640), (4, 2, 0, 600), (4, 2, 200, 600),
        # ... tiles of 512 with the window one tile long (no inside tile)
        # and two (an inside tile between window edge and diagonal) ...
        (4, 2, 512, 1024), (4, 2, 512, 1536), (2, 1, 1024, 2048),
        # ... and a single tile, which takes the masked body alone
        (4, 2, 0, 96), (4, 2, 40, 96)],
        ids=["4over2-w8", "4over2-global", "4over1-w130", "6over2-w200-pad",
             "2over2-w70", "s640-global", "s640-w200", "s640-w256",
             "s640-w128", "s640-w-over-s", "s600-pad-global", "s600-pad-w200",
             "s1024-w512", "s1536-w512", "s2048-w1024", "s96-global",
             "s96-w40"])
    def test_flash_against_jnp_forward_and_gradient(self, heads, kv, window,
                                                    seq):
        """The flash kernels in the interpreter (the mask on the tiles it
        cuts alone, tiles outside it skipped, key/value heads by index
        map) against the jnp path with an explicit mask and repeated
        heads."""
        ks = jax.random.split(jax.random.PRNGKey(seq + window), 4)
        q = jax.random.normal(ks[0], (2, seq, heads, 16))
        k = jax.random.normal(ks[1], (2, seq, kv, 16))
        v = jax.random.normal(ks[2], (2, seq, kv, 16))
        w = jax.random.normal(ks[3], (2, seq, heads, 16))

        def scalar(flash):
            return lambda q, k, v: jnp.sum(w * attention(
                q, k, v, causal=True, window=window, use_flash=flash))
        assert rel(attention(q, k, v, causal=True, window=window,
                             use_flash=True),
                   attention(q, k, v, causal=True, window=window)) < 2e-6
        got = jax.grad(scalar(True), (0, 1, 2))(q, k, v)
        want = jax.grad(scalar(False), (0, 1, 2))(q, k, v)
        for g, t in zip(got, want):
            assert rel(g, t) < 5e-6

    def test_window_changes_the_result_and_needs_causal(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
        full = attention(q, q, q, causal=True)
        cut = attention(q, q, q, causal=True, window=8)
        np.testing.assert_allclose(cut[:, :8], full[:, :8], atol=1e-6)
        assert rel(cut[:, 8:], full[:, 8:]) > 0.05
        with pytest.raises(ValueError, match="causal"):
            attention(q, q, q, window=8)


MOE = """type: "MoE" top: "rows" loss_weight: 0 loss_weight: 0
    moe_param {{ num_experts: 8 hidden_dim: 32 top_k: 2 dropless: true
        experts_held: {held} first_expert: {first}
        weight_filler {{ type: "gaussian" std: 0.2 }} }}"""


def moe_reference(lp, m, h, first, held):
    sz = dataclasses.replace(SZ, experts_held=held, first_expert=first)
    ref = {"gate": lp["w1"], "up": lp["w3"], "down": lp["w2"]}
    with jax.default_matmul_precision("highest"):
        return lm_ref.experts(ref, m, h @ lp["gate"], sz, None)


class TestDroplessExperts:
    @pytest.mark.parametrize("first,held", [(0, 8), (0, 2), (4, 2), (6, 2)])
    def test_against_reference(self, first, held):
        net = one_layer(MOE.format(held=held, first=first))
        lp, blobs = run_layer(net, X)
        assert rel(blobs["y"], moe_reference(lp, X, X, first, held)) < 2e-6
        assert np.asarray(blobs["rows"]).shape == (held,)
        if held == 8:   # every (token, choice) pair has a row
            assert float(jnp.sum(blobs["rows"])) == 2 * 32 * 2

    def test_skewed_router_drops_nothing(self):
        """One expert takes over half the rows; the capacity formulation
        would drop most of them, this one none."""
        net = one_layer(MOE.format(held=8, first=0))
        params, state = net.init(jax.random.PRNGKey(0))
        # expert 3's score is large for every token: each token picks it
        params["l"]["gate"] = params["l"]["gate"].at[:, 3].set(
            20.0 * jnp.sign(jnp.mean(X, axis=(0, 1))))
        x = X + 2.0 * jnp.sign(jnp.mean(X, axis=(0, 1)))
        blobs, _, _ = net.apply(params, state, {"x": x}, train=True,
                                rng=None)
        rows = np.asarray(blobs["rows"])
        assert rows[3] == 64 and rows[3] >= 0.5 * rows.sum()
        want = moe_reference(params["l"], x, x, 0, 8)
        assert rel(blobs["y"], want) < 2e-6

    def test_router_reads_the_second_bottom(self):
        net = net_from(f"""
            layer {{ name: "in" type: "Input" top: "x" top: "h"
                     input_param {{ shape {{ dim: 2 dim: 32 dim: 64 }}
                                    shape {{ dim: 2 dim: 32 dim: 64 }} }} }}
            layer {{ name: "l" bottom: "x" bottom: "h" top: "y"
                     {MOE.format(held=8, first=0)} }}""")
        params, state = net.init(jax.random.PRNGKey(0))
        h = jax.random.normal(jax.random.PRNGKey(9), X.shape)
        blobs, _, _ = net.apply(params, state, {"x": X, "h": h}, train=True,
                                rng=None)
        assert rel(blobs["y"], moe_reference(params["l"], X, h, 0, 8)) < 2e-6
        assert rel(blobs["y"], moe_reference(params["l"], X, X, 0, 8)) > 0.1

    def test_the_four_shares_add_up_to_the_uncut_layer(self):
        """first_expert 0, 2, 4, 6 at 2 held of 8: the partial results sum
        to the uncut reference's layer output (router weights are not
        renormalised over the held experts)."""
        whole = one_layer(MOE.format(held=8, first=0))
        lp, _ = run_layer(whole, X)
        total = jnp.zeros_like(X)
        for first in (0, 2, 4, 6):
            share = one_layer(MOE.format(held=2, first=first))
            params, state = share.init(jax.random.PRNGKey(0))
            params["l"] = {"gate": lp["gate"],
                           **{k: lp[k][first:first + 2]
                              for k in ("w1", "w2", "w3")}}
            blobs, _, _ = share.apply(params, state, {"x": X}, train=True,
                                      rng=None)
            total = total + blobs["y"]
        assert rel(total, moe_reference(lp, X, X, 0, 8)) < 2e-6

    def test_rows_past_the_last_group_never_reach_a_gradient(self,
                                                             monkeypatch):
        """On the chip a grouped product leaves the rows past its last
        group unwritten, forward and backward (the CPU's `ragged_dot`
        zero-fills them, which hid two leaks into the gradients on the
        first chip runs). Poison those rows with NaN in both passes: the
        result and every gradient must stay what they were."""
        @jax.custom_vjp
        def poisoned(rows, bank, sizes):
            return fwd(rows, bank, sizes)[0]

        def dead(rows, sizes):
            return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

        def fwd(rows, bank, sizes):
            out = jax.lax.ragged_dot(rows, bank, sizes)
            return jnp.where(dead(out, sizes), jnp.nan, out), (rows, bank,
                                                               sizes)

        def bwd(res, g):
            rows, bank, sizes = res
            clean = jnp.where(dead(g, sizes), 0.0, g)
            d_rows, d_bank = jax.vjp(
                lambda r, b: jax.lax.ragged_dot(r, b, sizes), rows, bank
            )[1](clean)
            return jnp.where(dead(d_rows, sizes), jnp.nan, d_rows), d_bank, \
                None
        poisoned.defvjp(fwd, bwd)

        net = one_layer(MOE.format(held=2, first=4))
        params, state = net.init(jax.random.PRNGKey(0))

        def scalar(params, x):
            blobs, _, _ = net.apply(params, state, {"x": x}, train=True,
                                    rng=None)
            return jnp.sum(blobs["y"] * X[::-1])
        want = jax.value_and_grad(scalar, (0, 1))(params, X)
        monkeypatch.setattr(moe_ops, "grouped_dot", poisoned)
        got = jax.value_and_grad(scalar, (0, 1))(params, X)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert bool(jnp.all(jnp.isfinite(g)))
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("top_k", [1, 2, 6])
    def test_route_is_top_k_then_softmax(self, top_k):
        logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
        w, ids = moe_ops.route(logits, top_k)
        top = jnp.sort(logits, axis=-1)[:, ::-1][:, :top_k]
        np.testing.assert_array_equal(
            jnp.take_along_axis(logits, ids, axis=-1), top)
        np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-6)
        # the same ratios as softmax over all, select, renormalise
        np.testing.assert_allclose(w / w[:, :1],
                                   jnp.exp(top - top[:, :1]), rtol=1e-5)
        assert w.dtype == jnp.float32

    def test_a_frozen_router_gets_no_gradient_and_no_update(self):
        """`param { lr_mult: 0 }` on the layer's first blob (the recipe's
        routers): the router still carries the gradient to its input."""
        net = one_layer('param { lr_mult: 0 } ' + MOE.format(held=2, first=4))
        params, state = net.init(jax.random.PRNGKey(0))

        def scalar(params, x):
            blobs, _, _ = net.apply(params, state, {"x": x}, train=True,
                                    rng=None)
            return jnp.sum(blobs["y"] * X[::-1])
        g_params, g_x = jax.grad(scalar, (0, 1))(params, X)
        assert float(jnp.max(jnp.abs(g_params["l"]["gate"]))) == 0.0
        assert float(jnp.max(jnp.abs(g_params["l"]["w1"]))) > 0.0
        free = one_layer(MOE.format(held=2, first=4))
        np.testing.assert_allclose(
            g_x, jax.grad(lambda x: jnp.sum(free.apply(
                params, state, {"x": x}, train=True, rng=None)[0]["y"]
                * X[::-1]))(X), rtol=1e-6, atol=1e-7)

    def test_capacity_path_refuses_a_share(self):
        with pytest.raises(ValueError, match="dropless"):
            one_layer("""type: "MoE" moe_param { num_experts: 8
                hidden_dim: 32 experts_held: 4 }""")
        with pytest.raises(ValueError, match="num_experts"):
            one_layer("""type: "MoE" moe_param { num_experts: 8
                hidden_dim: 32 dropless: true experts_held: 4
                first_expert: 6 }""")


def whole_net(precision: str):
    net = net_from(open(TINY).read(), precision)
    params, state = net.init(jax.random.PRNGKey(1))
    # norm scales away from their constant 1, so that they matter
    for name in params:
        if "scale" in params[name]:
            params[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), params[name]["scale"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    feeds = {"tokens": tokens, "label": jnp.roll(tokens, -1, axis=1)}

    def system(params):
        blobs, _, loss = net.apply(params, state, feeds, train=True,
                                   rng=jax.random.PRNGKey(0))
        return loss, blobs["logits"]
    (loss, logits), grads = jax.value_and_grad(system, has_aux=True)(params)
    ref = lm_ref.from_net(params, SZ)
    want_loss, want_grads = lm_ref.loss_and_grads(
        ref, feeds["tokens"], feeds["label"], SZ)
    got_grads = lm_ref.from_net(grads, SZ)
    # the recipe freezes the routers (lr_mult 0): the program computes no
    # gradient for them, the reference does
    frozen = [g.pop("router") for g in got_grads["layers"]]
    for g in want_grads["layers"]:
        del g["router"]
    return {"logits": rel(logits, lm_ref.forward(ref, tokens, SZ)),
            "loss": abs(float(loss) - float(want_loss)) / float(want_loss),
            "grads": tree_rel(got_grads, want_grads),
            "router_grads": max(float(jnp.max(jnp.abs(g))) for g in frozen)}


@pytest.fixture(scope="module")
def distances():
    return {p: whole_net(p) for p in ("f32", "bf16")}


class TestWholeNet:
    """The generated tiny net against the reference: logits, loss and every
    parameter's gradient. f32 agrees to rounding; bf16 stands about its
    own rounding (2^-8) away, and the tolerance between the two (3e-2
    against 1e-5) is what tells a bf16 net from a broken one."""

    @pytest.mark.parametrize("what", ["logits", "loss", "grads"])
    def test_f32_to_rounding(self, distances, what):
        assert distances["f32"][what] < 1e-5

    @pytest.mark.parametrize("what,floor", [("logits", 1e-3),
                                            ("loss", 0.0), ("grads", 1e-3)])
    def test_bf16_off_by_its_rounding(self, distances, what, floor):
        assert floor <= distances["bf16"][what] < 3e-2

    def test_the_frozen_routers_have_no_gradient(self, distances):
        assert distances["f32"]["router_grads"] == 0.0

    def test_reference_counts_match_the_built_net(self):
        net = net_from(open(TINY).read())
        params, _ = net.init(jax.random.PRNGKey(0))
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
        assert n == lm_ref.param_count(SZ)
        from caffe_mpi_tpu.utils.flops import net_macs_per_image
        # the program's own MAC model (proto/netshape.py) counts the same
        # projections, visible pairs, router, expected held rows and head
        assert net_macs_per_image(net) == lm_ref.macs_per_sample(SZ, 32)


class TestTokenIdsSurviveBf16:
    def test_ids_past_256_reach_embed_and_loss_exactly(self):
        """bf16 holds integers exactly only up to 256. At the real
        vocabulary size a token id that went through the compute type
        would select another row (37983 reads 37888): integer feeds must
        reach Embed and the loss as integers."""
        vocab = 37984
        net = net_from(f"""
            layer {{ name: "tokens" type: "Input" top: "tokens" top: "label"
                     input_param {{ shape {{ dim: 1 dim: 8 }}
                                    shape {{ dim: 1 dim: 8 }} }} }}
            layer {{ name: "embed" type: "Embed" bottom: "tokens" top: "e"
                     embed_param {{ input_dim: {vocab} num_output: 2
                                    bias_term: false }} }}
            layer {{ name: "logits" type: "InnerProduct" bottom: "e"
                     top: "logits" inner_product_param {{
                       num_output: {vocab} axis: 2 bias_term: false }} }}
            layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
                     bottom: "label" top: "loss"
                     softmax_param {{ axis: 2 }} }}""", "bf16", batch=1)
        ids = jnp.array([[257, 1001, 37983, 12345, 30001, 511, 4099, 263]],
                        jnp.int32)
        table = jnp.stack([jnp.arange(vocab, dtype=jnp.float32) % 251,
                           jnp.arange(vocab, dtype=jnp.float32) % 241], 1)
        head = jnp.zeros((vocab, 2)).at[ids[0], 0].set(8.0)
        params = {"embed": {"weight": table}, "logits": {"weight": head}}
        feeds = {"tokens": ids, "label": ids}
        blobs, _, loss = net.apply(params, {}, feeds, train=True, rng=None)
        np.testing.assert_array_equal(
            np.asarray(blobs["e"], np.float32)[0],
            np.asarray(table[ids[0]].astype(jnp.bfloat16), np.float32))
        picked = np.asarray(jnp.take_along_axis(
            blobs["logits"].astype(jnp.float32), ids[..., None],
            axis=-1))[0, :, 0]
        want = 8.0 * np.asarray(table[ids[0], 0].astype(jnp.bfloat16),
                                np.float32)
        np.testing.assert_allclose(picked, want, rtol=1e-2)
        # the loss picked each position's own label: the row whose head
        # weight is set is by far the largest wherever e[0] > 0
        rows = np.asarray(table[ids[0], 0]) > 0
        nll = -np.asarray(jax.nn.log_softmax(
            blobs["logits"].astype(jnp.float32), -1))[0, np.arange(8),
                                                      np.asarray(ids[0])]
        np.testing.assert_allclose(float(loss), nll.mean(), rtol=1e-3)
        assert rows.any()
