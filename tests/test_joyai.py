"""JoyAI-LLM-Flash through prototxt -> Net, at a tiny size on the CPU,
against the benchmark's plain reference (benchmarks/reference/
joyai_ref.py): D 64, 4 heads of 16 + 8 rotary query/key lanes and 16 value
lanes over latent ranks 48 / 32, S 32, a dense block of width 96 and two
expert blocks of 32 experts (2 held, 4 a token, width 32, one shared),
the MTP module, vocabulary 64 — the sizes of
`models/joyai_llm_flash/tiny_train_val.prototxt`, which the same generator
emits as the benchmark's recipe.

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

from reference import joyai_ref  # noqa: E402

from caffe_mpi_tpu.net import Net  # noqa: E402
from caffe_mpi_tpu.ops import moe as moe_ops  # noqa: E402
from caffe_mpi_tpu.ops.attention import attention, rope_pairs  # noqa: E402
from caffe_mpi_tpu.proto import NetParameter  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "joyai_llm_flash.json")))
SZ = joyai_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY = os.path.join(ROOT, "models", "joyai_llm_flash",
                    "tiny_train_val.prototxt")


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def net_from(text: str, batch: int = 2) -> Net:
    npar = NetParameter.from_text(text)
    for lp in npar.layer:
        if lp.type == "Input":
            for shape in lp.input_param.shape:
                shape.dim[0] = batch
    return Net(npar, phase="TRAIN", precision="f32")


def tokens(batch=2, seq=32, seed=1) -> dict:
    t = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                           SZ.vocab)
    return {"tokens": t, "label": jnp.roll(t, -1, 1),
            "label_mtp": jnp.roll(t, -2, 1)}


# -- the flash kernels at unequal widths -------------------------------------

@pytest.fixture(scope="module", params=[128, 200, 384],
                ids=["one_tile", "pads_to_256", "three_tiles"])
def flash_pair(request):
    """(jnp, flash): out and the three gradients at query/key width 192
    and value width 128, the second through the interpreted kernels."""
    s = request.param
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k = (jax.random.normal(key, (1, s, 2, 192)) for key in ks[:2])
    v, do = (jax.random.normal(key, (1, s, 2, 128)) for key in ks[2:])

    def run(flash):
        out, vjp = jax.vjp(lambda q, k, v: attention(
            q, k, v, causal=True, use_flash=flash), q, k, v)
        return (out, *vjp(do))
    return run(False), run(True)


@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_flash_at_192_and_128_is_the_jnp_path(flash_pair, which):
    want, got = flash_pair
    assert got[which].shape == want[which].shape
    assert got[which].shape[-1] == (128, 192, 192, 128)[which]
    assert rel(got[which], want[which]) < 2e-6


def test_scores_are_scaled_by_the_query_width():
    """1 / sqrt(192), not the values' 128: a key equal to the query and one
    orthogonal to it leave softmax weights one can write down."""
    q = jnp.zeros((1, 2, 1, 192)).at[0, :, 0, 0].set(3.0)
    k = jnp.zeros((1, 2, 1, 192)).at[0, 0, 0, 0].set(3.0)
    v = jnp.zeros((1, 2, 1, 128)).at[0, 0, 0, 0].set(1.0)
    for flash in (False, True):
        out = attention(q, k, v, causal=True, use_flash=flash)
        a = np.exp(9.0 / np.sqrt(192.0))
        assert abs(float(out[0, 1, 0, 0]) - a / (a + 1.0)) < 1e-6


# -- the latent attention layer ----------------------------------------------

LATENT = """attention_param { num_heads: 4 causal: true bias_term: false
    rope_theta: 1000.0 q_lora_rank: 48 kv_lora_rank: 32
    qk_nope_head_dim: 16 qk_rope_head_dim: 8 v_head_dim: 16
    rope_interleave: true use_flash: %s
    weight_filler { type: "gaussian" std: 0.2 } }"""


def latent_layer(flash: bool):
    net = net_from("""
        layer { name: "in" type: "Input" top: "x"
                input_param { shape { dim: 2 dim: 32 dim: 64 } } }
        layer { name: "l" type: "Attention" bottom: "x" top: "y" %s }"""
                   % (LATENT % str(flash).lower()))
    params, state = net.init(jax.random.PRNGKey(5))
    return net, params, state


X = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64), jnp.float32)


def by_hand_pairs(x, theta):
    """(S, d) -> adjacent pairs turned by pos * theta^(-2i/d), in numpy."""
    x = np.asarray(x, np.float64)
    out = np.empty_like(x)
    d = x.shape[-1]
    for pos in range(x.shape[0]):
        for i in range(d // 2):
            a = pos * theta ** (-2.0 * i / d)
            x0, x1 = x[pos, 2 * i], x[pos, 2 * i + 1]
            out[pos, 2 * i] = x0 * np.cos(a) - x1 * np.sin(a)
            out[pos, 2 * i + 1] = x1 * np.cos(a) + x0 * np.sin(a)
    return out


class TestLatentAttention:
    def test_rope_pairs_by_hand(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 8))
        got = rope_pairs(x, 100.0)
        for head in range(2):
            assert rel(got[0, :, head], by_hand_pairs(x[0, :, head],
                                                      100.0)) < 1e-6

    @pytest.mark.parametrize("what", ["q", "k", "v"])
    def test_only_the_rotary_lanes_turn(self, what):
        """q = [q_n | turned q_r] a head, k = [k_n | the ONE turned rotary
        key, the same under every head], v untouched: each against the
        blobs' products and the by-hand rotation."""
        net, params, _ = latent_layer(False)
        layer, p = net.layers[-1], params["l"]
        q, k, v = layer._latent_qkv(p, X)
        assert q.shape == k.shape == (2, 32, 4, 24) and v.shape[-1] == 16
        f64 = lambda a: np.asarray(a, np.float64)
        x = f64(X[1])

        def rms(t, g):
            return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) \
                * f64(g)
        if what == "q":
            raw = (rms(x @ f64(p["q_a_weight"]).T, p["q_norm"])
                   @ f64(p["q_b_weight"]).T).reshape(32, 4, 24)
            got = f64(q[1])
        else:
            down = x @ f64(p["kv_a_weight"]).T
            kv = (rms(down[:, :32], p["kv_norm"])
                  @ f64(p["kv_b_weight"]).T).reshape(32, 4, 32)
            if what == "v":
                assert rel(v[1], kv[..., 16:]) < 1e-5
                return
            raw = np.concatenate(
                [kv[..., :16], np.repeat(down[:, None, 32:], 4, 1)], -1)
            got = f64(k[1])
        assert rel(got[..., :16], raw[..., :16]) < 1e-5   # untouched
        for head in range(4):
            assert rel(got[:, head, 16:],
                       by_hand_pairs(raw[:, head, 16:], 1000.0)) < 1e-5
        # and they do turn: position 0 alone keeps its value
        assert rel(got[1:, :, 16:], raw[1:, :, 16:]) > 0.1

    @pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
    def test_layer_against_the_reference(self, flash):
        net, params, state = latent_layer(flash)
        blobs, _, _ = net.apply(params, state, {"x": X}, train=True,
                                rng=jax.random.PRNGKey(0))
        p = params["l"]
        sz = dataclasses.replace(SZ, rope_theta=1000.0)
        lp = {"w_dq": p["q_a_weight"].T, "g_q": p["q_norm"],
              "w_uq": p["q_b_weight"].T, "w_dkv": p["kv_a_weight"].T,
              "g_kv": p["kv_norm"], "w_ukv": p["kv_b_weight"].T,
              "w_o": p["proj_weight"].T}
        with jax.default_matmul_precision("highest"):
            want = joyai_ref.attention(lp, X, sz, 16, None)
        assert rel(blobs["y"], want) < 1e-5

    def test_a_latent_layer_refuses_what_has_no_meaning_there(self):
        with pytest.raises(ValueError, match="latent attention"):
            net_from("""
                layer { name: "in" type: "Input" top: "x"
                        input_param { shape { dim: 1 dim: 8 dim: 64 } } }
                layer { name: "l" type: "Attention" bottom: "x" top: "y"
                        attention_param { num_heads: 4 kv_lora_rank: 32
                            q_lora_rank: 48 qk_nope_head_dim: 16
                            qk_rope_head_dim: 8 v_head_dim: 16
                            rope_theta: 10.0 bias_term: false
                            window: 4 causal: true } }""")


# -- the router ---------------------------------------------------------------

class TestSigmoidRouting:
    LOGITS = jax.random.normal(jax.random.PRNGKey(3), (64, 32)) * 2.0
    BIAS = jax.random.normal(jax.random.PRNGKey(4), (32,)) * 0.5

    def test_the_bias_selects_and_does_not_weigh(self):
        w, ids = moe_ops.route(self.LOGITS, 4, "sigmoid", self.BIAS, 2.5)
        plain_w, plain_ids = moe_ops.route(self.LOGITS, 4, "sigmoid", None,
                                           2.5)
        s = 1.0 / (1.0 + np.exp(-np.asarray(self.LOGITS, np.float64)))
        want_ids = np.argsort(-(s + np.asarray(self.BIAS, np.float64)),
                              axis=1)[:, :4]
        assert np.array_equal(np.sort(np.asarray(ids), 1),
                              np.sort(want_ids, 1))
        chosen = np.take_along_axis(s, np.asarray(ids), 1)
        assert rel(w, 2.5 * chosen / chosen.sum(1, keepdims=True)) < 1e-6
        assert np.allclose(np.asarray(w).sum(1), 2.5, atol=1e-5)
        # the bias changes who is chosen, and with it the weights
        moved = np.any(np.sort(np.asarray(ids), 1)
                       != np.sort(np.asarray(plain_ids), 1), axis=1)
        assert moved.mean() > 0.3
        assert rel(np.sort(np.asarray(w), 1),
                   np.sort(np.asarray(plain_w), 1)) > 0.01
        # and a bias that weighed would give other weights than these
        biased = chosen + np.asarray(self.BIAS, np.float64)[np.asarray(ids)]
        assert rel(2.5 * biased / biased.sum(1, keepdims=True), w) > 0.05

    def test_a_level_shift_of_the_bias_changes_nothing(self):
        a = moe_ops.route(self.LOGITS, 4, "sigmoid", self.BIAS, 2.5)
        b = moe_ops.route(self.LOGITS, 4, "sigmoid", self.BIAS - 0.75, 2.5)
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))

    def test_unknown_scoring_is_refused(self):
        with pytest.raises(ValueError, match="softmax or sigmoid"):
            moe_ops.route(self.LOGITS, 4, "tanh")


# -- the expert layer ----------------------------------------------------------

MOE = """type: "MoE" top: "rows" moe_param { num_experts: 32 hidden_dim: 32
    top_k: 4 dropless: true experts_held: %d first_expert: %d
    scoring: "sigmoid" routed_scaling_factor: 2.5 activation: "silu"
    shared_experts: 1 weight_filler { type: "gaussian" std: 0.2 }
    bias_filler { type: "gaussian" mean: -0.8 std: 0.05 } }"""


def moe_layer(held: int, first: int):
    net = net_from("""
        layer { name: "in" type: "Input" top: "x"
                input_param { shape { dim: 2 dim: 32 dim: 64 } } }
        layer { name: "l" bottom: "x" bottom: "x" top: "y" %s }"""
                   % (MOE % (held, first)))
    return net


def moe_as_reference(p) -> dict:
    return {"router": p["gate"], "bias": p["select_bias"], "gate": p["w1"],
            "up": p["w3"], "down": p["w2"], "s_gate": p["shared_w1"],
            "s_up": p["shared_w3"], "s_down": p["shared_w2"]}


class TestSharedAndRoutedExperts:
    @pytest.fixture(scope="class")
    def whole(self):
        net = moe_layer(32, 0)
        params, state = net.init(jax.random.PRNGKey(11))
        blobs, _, _ = net.apply(params, state, {"x": X}, train=True,
                                rng=jax.random.PRNGKey(0))
        sz = dataclasses.replace(SZ, experts_held=32)
        with jax.default_matmul_precision("highest"):
            want = joyai_ref.feed_forward(moe_as_reference(params["l"]), X,
                                          sz, None)
        return params["l"], blobs, want

    def test_the_whole_layer_is_the_reference(self, whole):
        _, blobs, want = whole
        assert rel(blobs["y"], want) < 1e-5
        assert float(jnp.sum(blobs["rows"])) == 2 * 32 * 4

    def test_sixteen_shares_and_one_shared_expert_add_up(self, whole):
        """THE share test: what the 16 shares of 2 experts give, with the
        shared expert (which every share computes for its own tokens)
        counted once, is the uncut reference's whole layer."""
        p, _, want = whole
        with jax.default_matmul_precision("highest"):
            shared = joyai_ref.gated(X, p["shared_w1"], p["shared_w3"],
                                     p["shared_w2"], None)
        total, rows = jnp.zeros_like(X), 0.0
        for share in range(16):
            net = moe_layer(2, 2 * share)
            mine = {**p, **{k: p[k][2 * share:2 * share + 2]
                            for k in ("w1", "w2", "w3")}}
            blobs, _, _ = net.apply({"l": mine}, {}, {"x": X}, train=True,
                                    rng=jax.random.PRNGKey(0))
            total = total + (blobs["y"] - shared)    # its routed part
            rows += float(jnp.sum(blobs["rows"]))
        assert rows == 2 * 32 * 4          # every (token, choice) pair once
        assert rel(total + shared, want) < 1e-5
        assert rel(shared, want) > 0.1     # the shared expert is not nothing

    def test_the_reference_s_own_shares_add_up(self, whole):
        p, _, want = whole
        lp = moe_as_reference(p)
        with jax.default_matmul_precision("highest"):
            parts = sum(joyai_ref.routed(
                {**lp, **{k: lp[k][2 * i:2 * i + 2]
                          for k in ("gate", "up", "down")}},
                X, SZ, None, first_expert=2 * i, held=2) for i in range(16))
            shared = joyai_ref.gated(X, lp["s_gate"], lp["s_up"],
                                     lp["s_down"], None)
        assert rel(parts + shared, want) < 1e-5

    @pytest.mark.parametrize("field,text", [
        ("scoring", 'scoring: "sigmoid"'), ("activation", 'activation: "silu"'),
        ("shared_experts", "shared_experts: 1")])
    def test_the_capacity_path_refuses_the_dropless_parameters(self, field,
                                                               text):
        with pytest.raises(ValueError, match="need dropless"):
            net_from("""
                layer { name: "in" type: "Input" top: "x"
                        input_param { shape { dim: 1 dim: 8 dim: 16 } } }
                layer { name: "l" type: "MoE" bottom: "x" top: "y"
                        moe_param { num_experts: 4 hidden_dim: 8 %s } }"""
                     % text)


def dropless_as_it_was(params, x, router_in, *, top_k, first_expert=0):
    """`ops/moe.py moe_dropless` before it took parameters (PR 30's body,
    from the module's own pieces): top-k then softmax, gated ReLU."""
    held = params["w1"].shape[0]
    logits = jnp.dot(router_in, params["gate"],
                     preferred_element_type=jnp.float32)
    top, ids = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    weights = jax.nn.softmax(top, axis=-1)
    local = ids.T.reshape(-1) - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    live = local[order] < held
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    xs = jnp.where(live[:, None], moe_ops._dispatch(x, order, inv, top_k), 0)
    h = jax.nn.relu(moe_ops.grouped_dot(xs, params["w1"], sizes)) \
        * moe_ops.grouped_dot(xs, params["w3"], sizes)
    ys = moe_ops.grouped_dot(h, params["w2"], sizes)
    w = moe_ops._permute(weights.T.reshape(-1), order, inv)
    ys = jnp.where(live[:, None], ys, 0) * w[:, None].astype(ys.dtype)
    return moe_ops._combine(ys, order, inv, top_k), sizes.astype(jnp.float32)


@pytest.mark.parametrize("what", ["y", "rows", "grads"])
def test_dropless_defaults_are_bit_for_bit_the_layer_as_it_was(what):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    params = {"gate": jax.random.normal(ks[0], (64, 8)),
              "w1": jax.random.normal(ks[1], (2, 64, 32)) * 0.2,
              "w3": jax.random.normal(ks[2], (2, 64, 32)) * 0.2,
              "w2": jax.random.normal(ks[3], (2, 32, 64)) * 0.2}
    x = jax.random.normal(ks[4], (48, 64))

    def run(fn):
        def loss(params, x):
            y, rows = fn(params, x, x, top_k=2, first_expert=4)
            return jnp.sum(y * y), (y, rows)
        (_, (y, rows)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        return {"y": y, "rows": rows, "grads": grads}[what]
    got, want = run(moe_ops.moe_dropless), run(dropless_as_it_was)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- the whole net --------------------------------------------------------------

@pytest.fixture(scope="module", params=[False, True], ids=["jnp", "flash"])
def whole_net(request):
    """The tiny recipe's logits, loss and gradient, and the reference's, on
    seeded weights; the flash path through the interpreted kernels."""
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
        return joyai_ref.loss(joyai_ref.from_net(p, SZ), feeds["tokens"],
                              feeds["label"], feeds["label_mtp"], SZ)
    want_loss, want_grads = jax.value_and_grad(reference)(params)
    want_logits = joyai_ref.forward(joyai_ref.from_net(params, SZ),
                                    feeds["tokens"], feeds["label"], SZ)
    return {"net": net, "params": params, "state": state, "feeds": feeds,
            "loss": (loss, want_loss), "grads": (grads, want_grads),
            "logits": (blobs["logits"], want_logits[0]),
            "mtp_logits": (blobs["mtp/logits"], want_logits[1]),
            "blobs": blobs}


class TestWholeNet:
    @pytest.mark.parametrize("what", ["logits", "mtp_logits", "loss"])
    def test_against_the_reference(self, whole_net, what):
        got, want = whole_net[what]
        assert rel(got, want) < 1e-4

    def test_every_leaf_s_gradient_is_the_reference_s(self, whole_net):
        got, want = whole_net["grads"]
        frozen = {(l, b) for l, b, d in
                  whole_net["net"].learnable_param_decls()
                  if d.lr_mult == 0.0}
        assert frozen == {(f"{b}/moe", k) for b in ("blk1", "blk2", "mtp")
                          for k in ("gate", "select_bias")}
        checked = 0
        for layer, blobs in want.items():
            for blob, g in blobs.items():
                if (layer, blob) in frozen:
                    assert not np.any(np.asarray(got[layer][blob]))
                    continue
                assert rel(got[layer][blob], g) < 1e-4, (layer, blob)
                checked += 1
        assert checked == 64

    def test_the_loss_is_main_plus_three_tenths_of_mtp(self, whole_net):
        blobs = whole_net["blobs"]
        loss, _ = whole_net["loss"]
        assert abs(float(loss) - float(blobs["loss"])
                   - 0.3 * float(blobs["mtp/loss"])) < 1e-5

    def test_rows_count_every_pair_routed_here(self, whole_net):
        blobs = whole_net["blobs"]
        for b in ("blk1", "blk2", "mtp"):
            rows = np.asarray(blobs[f"{b}/moe_rows"])
            assert rows.shape == (2,) and 0 < rows.sum() <= 2 * 32 * 4


def test_shared_arrays_receive_both_paths_gradients():
    """The embedding table and the head are the trunk's and the MTP
    module's by name: the shared array's gradient is the sum of what each
    site of an unshared copy of the net receives."""
    text = open(TINY).read().replace("use_flash: true", "use_flash: false")
    feeds = tokens()

    def grads(net, params):
        return jax.grad(lambda p: net.apply(
            p, {}, feeds, train=True, rng=jax.random.PRNGKey(0))[2])(params)
    shared = net_from(text)
    params, _ = shared.init(jax.random.PRNGKey(3))
    assert "mtp/embed" not in params and "mtp/logits" not in params
    apart = net_from(text.replace('name: "embed_w"', 'name: "embed_w2"', 2)
                     .replace('name: "embed_w2"', 'name: "embed_w"', 1)
                     .replace('name: "head_w"', 'name: "head_w2"', 2)
                     .replace('name: "head_w2"', 'name: "head_w"', 1))
    params2 = {**params, "mtp/embed": params["embed"],
               "mtp/logits": params["logits"]}
    g1, g2 = grads(shared, params), grads(apart, params2)
    for trunk, mtp in (("embed", "mtp/embed"), ("logits", "mtp/logits")):
        a, b = g2[trunk]["weight"], g2[mtp]["weight"]
        assert float(jnp.linalg.norm(a)) > 0 < float(jnp.linalg.norm(b))
        assert rel(g1[trunk]["weight"], a + b) < 1e-5
        assert rel(g1[trunk]["weight"], a) > 1e-3
