"""The attention layer's head-major path (PR 34): q, k and v leave their
projections (B, H, S, D), the order the flash kernels read, the per-head
norm and the rotary turn are lane-local, and the output projection
contracts the kernels' own result. The mathematics and the roundings are
what they were: the expressions of before the change are kept HERE as
oracles (`old_*`), and the declared blobs are held to their literal names,
shapes and order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffe_mpi_tpu.core.types import DtypePolicy
from caffe_mpi_tpu.io import load_caffemodel, save_caffemodel
from caffe_mpi_tpu.net import Net
from caffe_mpi_tpu.ops.attention import (attention, lane_partner, rope,
                                         rope_pairs, rope_tables, turn_lanes)
from caffe_mpi_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_heads)
from caffe_mpi_tpu.proto import NetParameter

from gradcheck import make_layer


# -- the expressions as they stood before PR 34 ------------------------------

def old_rope(x, theta, period=0):
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.arange(s, dtype=jnp.float32)
    if period:
        pos = (jnp.arange(s) % period).astype(jnp.float32)
    ang = pos[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def old_rope_pairs(x, theta):
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.repeat(jnp.arange(s, dtype=jnp.float32)[:, None]
                     * inv[None, :], 2, axis=-1)
    even = jnp.arange(d) % 2 == 0
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.where(even, -jnp.sin(ang), jnp.sin(ang))[None, :, None, :]
    x32 = x.astype(jnp.float32)
    other = jnp.where(even, jnp.roll(x32, -1, axis=-1),
                      jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + other * sin).astype(x.dtype)


def old_rms_normalize(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


def old_apply(layer, params, x):
    """`AttentionLayer.apply` (one device, no mesh) as it was: one fused
    product, (B, S, H, D) tensors, slices and concatenations."""
    p = layer.p
    x = layer.f(x)
    n, s, c = x.shape
    w = lambda name: layer.f(params[name])
    if p.kv_lora_rank:
        nope, rot, vd = layer.latent
        rms = lambda t, scale: old_rms_normalize(t, p.norm_eps) * scale
        turn = old_rope_pairs if p.rope_interleave else old_rope
        q = rms(x @ w("q_a_weight").T, w("q_norm")) @ w("q_b_weight").T
        q = q.reshape(n, s, layer.heads, nope + rot)
        kv = x @ w("kv_a_weight").T
        k_r = turn(kv[..., None, p.kv_lora_rank:], p.rope_theta)
        kv = rms(kv[..., :p.kv_lora_rank], w("kv_norm")) \
            @ w("kv_b_weight").T
        kv = kv.reshape(n, s, layer.heads, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], turn(q[..., nope:], p.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r, (n, s, layer.heads, rot))], axis=-1)
        v = kv[..., nope:]
    else:
        qkv = x @ w("qkv_weight").T
        if p.bias_term:
            qkv = qkv + w("qkv_bias")
        q, k, v = jnp.split(qkv, [layer.nq, layer.nq + layer.nkv], axis=-1)
        q = q.reshape(n, s, layer.heads, layer.head_dim)
        k = k.reshape(n, s, layer.kv_heads, layer.head_dim)
        v = v.reshape(n, s, layer.kv_heads, layer.head_dim)
        if p.qk_norm:
            q = old_rms_normalize(q, p.norm_eps) * w("q_norm")
            k = old_rms_normalize(k, p.norm_eps) * w("k_norm")
        if p.rope_theta:
            period = s // 2 if p.block_diffusion else 0
            q = old_rope(q, p.rope_theta, period)
            k = old_rope(k, p.rope_theta, period)
    out = attention(q, k, v, causal=bool(p.causal),
                    use_flash=bool(p.use_flash), window=p.window,
                    block_diffusion=p.block_diffusion)
    y = out.reshape(n, s, layer.nq) @ w("proj_weight").T
    if p.bias_term:
        y = y + w("proj_bias")
    return y


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


# -- the rotary expressions --------------------------------------------------

class TestRotaryExpressions:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", ["halves", "halves_period", "pairs"])
    def test_new_expression_is_the_old_one(self, case, dtype):
        """x * cos + partner * sin with the sign folded into the table is
        x1 cos - x2 sin lane for lane: the same float32 terms, one
        rounding."""
        x = normal(0, (2, 24, 3, 16), dtype)
        if case == "pairs":
            new, old = rope_pairs(x, 1e4), old_rope_pairs(x, 1e4)
        else:
            period = 12 if case == "halves_period" else 0
            new, old = rope(x, 1e4, period), old_rope(x, 1e4, period)
        assert new.dtype == old.dtype == dtype
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(old, np.float32))

    @pytest.mark.parametrize("pairs", [False, True], ids=["halves", "pairs"])
    def test_partner_is_an_exact_permutation(self, pairs):
        x = normal(1, (2, 5, 3, 16), jnp.bfloat16)
        want = (jnp.where(jnp.arange(16) % 2 == 0, jnp.roll(x, -1, -1),
                          jnp.roll(x, 1, -1)) if pairs
                else jnp.roll(x, 8, -1))
        got = lane_partner(x, 16, pairs)
        assert got.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    @pytest.mark.parametrize("head_major", [False, True],
                             ids=["bshd", "bhsd"])
    @pytest.mark.parametrize("pairs", [False, True], ids=["halves", "pairs"])
    def test_lanes_without_positions_pass_bit_for_bit(self, pairs,
                                                      head_major):
        """Tables that are cos 1, sin 0 on the first lanes: x * 1 + 0 * 0
        there, the old turn of the last lanes alone on the others."""
        lead, rot = 16, 8
        x = normal(2, (2, 12, 3, lead + rot))
        cos, sin = rope_tables(12, rot, 1e3, pairs=pairs, lead=lead)
        t = x.transpose(0, 2, 1, 3) if head_major else x
        got = turn_lanes(t, lane_partner(t, rot, pairs), cos, sin,
                         head_major=head_major)
        got = got.transpose(0, 2, 1, 3) if head_major else got
        np.testing.assert_array_equal(np.asarray(got[..., :lead]),
                                      np.asarray(x[..., :lead]))
        old = (old_rope_pairs if pairs else old_rope)(x[..., lead:], 1e3)
        np.testing.assert_array_equal(np.asarray(got[..., lead:]),
                                      np.asarray(old))

    def test_tables_are_built_once_for_both_latent_operands(self):
        """The one rotary key head reads the last lanes of the tables the
        192-wide query reads."""
        cos, sin = rope_tables(12, 8, 1e3, pairs=True, lead=16)
        small = rope_tables(12, 8, 1e3, pairs=True)
        np.testing.assert_array_equal(np.asarray(cos[:, 16:]),
                                      np.asarray(small[0]))
        np.testing.assert_array_equal(np.asarray(sin[:, 16:]),
                                      np.asarray(small[1]))


# -- the kernels' head-major entry -------------------------------------------

ENTRY_CASES = {
    # (heads, kv heads, S, D, Dv, mask)
    "grouped_window": (4, 2, 160, 16, 16, dict(causal=True, window=48)),
    "block_mask": (4, 2, 128, 16, 16, dict(block_diffusion=4)),
    "latent_192_128": (2, 2, 128, 192, 128, dict(causal=True)),
}


def entry_operands(case, dtype=jnp.float32):
    h, hkv, s, d, dv, mask = ENTRY_CASES[case]
    return (normal(3, (2, s, h, d), dtype), normal(4, (2, s, hkv, d), dtype),
            normal(5, (2, s, hkv, dv), dtype), mask)


class TestHeadMajorEntry:
    @pytest.mark.parametrize("case", list(ENTRY_CASES))
    def test_forward(self, case):
        q, k, v, mask = entry_operands(case)
        turned = lambda t: t.transpose(0, 2, 1, 3)
        heads = flash_attention_heads(turned(q), turned(k), turned(v),
                                      **mask)
        assert heads.shape == (2, q.shape[2], q.shape[1], v.shape[3])
        # one code path below: the (B, S, H, D) entry is this one turned
        np.testing.assert_array_equal(
            np.asarray(turned(heads)),
            np.asarray(flash_attention(q, k, v, **mask)))
        assert rel(turned(heads), attention(q, k, v, **mask)) < 2e-6

    @pytest.mark.parametrize("case", list(ENTRY_CASES))
    def test_gradients(self, case):
        q, k, v, mask = entry_operands(case)
        turned = lambda t: t.transpose(0, 2, 1, 3)
        weigh = normal(6, (2, q.shape[1], q.shape[2], v.shape[3]))

        def loss(fn, head_major):
            def f(q, k, v):
                if head_major:
                    return jnp.sum(turned(fn(turned(q), turned(k),
                                             turned(v), **mask)) * weigh)
                return jnp.sum(fn(q, k, v, **mask) * weigh)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        heads = loss(flash_attention_heads, True)
        for got, same, ref in zip(heads, loss(flash_attention, False),
                                  loss(attention, False)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
            assert rel(got, ref) < 2e-5


# -- the layer, before and after ---------------------------------------------

LAYER_CASES = {
    "grouped_window_rope_flash": (
        "num_heads: 4 num_kv_heads: 2 head_dim: 16 causal: true window: 48 "
        "rope_theta: 10000.0 bias_term: false use_flash: true", (2, 160, 32)),
    "block_mask_qk_norm_flash": (
        "num_heads: 4 num_kv_heads: 2 head_dim: 16 block_diffusion: 4 "
        "qk_norm: true rope_theta: 10000.0 bias_term: false use_flash: true",
        (2, 128, 32)),
    "latent_flash": (
        "num_heads: 4 causal: true bias_term: false rope_theta: 1000.0 "
        "q_lora_rank: 48 kv_lora_rank: 32 qk_nope_head_dim: 16 "
        "qk_rope_head_dim: 8 v_head_dim: 16 rope_interleave: true "
        "use_flash: true", (2, 128, 64)),
    "latent_halves_jnp": (
        "num_heads: 4 causal: true bias_term: false rope_theta: 1000.0 "
        "q_lora_rank: 48 kv_lora_rank: 32 qk_nope_head_dim: 16 "
        "qk_rope_head_dim: 8 v_head_dim: 16", (2, 32, 64)),
    "biased_qk_norm_jnp": (
        "num_heads: 4 causal: true qk_norm: true rope_theta: 10000.0 "
        "bias_filler { type: \"gaussian\" std: 0.1 }", (2, 32, 32)),
}


def case_layer(case, policy=None):
    text, shape = LAYER_CASES[case]
    layer, params, _ = make_layer(
        'name: "attn" type: "Attention" bottom: "x" top: "y"\n'
        'attention_param { %s weight_filler { type: "gaussian" std: 0.2 } }'
        % text, [shape], policy=policy, seed=11)
    # norm scales off their constant 1, so that a scale applied to the
    # wrong lane shows
    for name in ("q_norm", "k_norm", "kv_norm"):
        if name in params:
            params[name] = 1.0 + 0.3 * normal(12, params[name].shape)
    return layer, params, normal(13, shape)


class TestLayerBeforeAndAfter:
    @pytest.mark.parametrize("case", list(LAYER_CASES))
    def test_float32_output_and_gradients(self, case):
        layer, params, x = case_layer(case)
        assert layer._head_major() == ("flash" in case)
        new = lambda p, x: layer.apply(p, {}, [x], train=True, rng=None)[0][0]
        old = lambda p, x: old_apply(layer, p, x)
        assert rel(new(params, x), old(params, x)) < 2e-6
        weigh = normal(14, x.shape[:2] + (new(params, x).shape[-1],))
        grads = [jax.grad(lambda p, x: jnp.sum(f(p, x) * weigh),
                          argnums=(0, 1))(params, x) for f in (new, old)]
        flat = [jax.tree.leaves(g) for g in grads]
        assert jax.tree.structure(grads[0]) == jax.tree.structure(grads[1])
        for got, want in zip(*flat):
            assert rel(got, want) < 2e-5

    @pytest.mark.parametrize("case", list(LAYER_CASES))
    def test_bf16_output_and_gradients_within_rounding(self, case):
        """Under the bf16 policy the two differ by where the MXU's float32
        sums are cut, never by a float32 tensor more or less: each stays
        as close to the float32 layer as the other."""
        bf16 = DtypePolicy(forward=jnp.bfloat16, backward=jnp.bfloat16)
        layer, params, x = case_layer(case, bf16)
        exact, _, _ = case_layer(case)
        new = lambda p, x: layer.apply(p, {}, [x], train=True, rng=None)[0][0]
        old = lambda p, x: old_apply(layer, p, x)
        ref = lambda p, x: old_apply(exact, p, x)
        assert new(params, x).dtype == jnp.bfloat16
        weigh = normal(14, x.shape[:2] + (new(params, x).shape[-1],))
        loss = lambda f: (lambda p, x: jnp.sum(
            f(p, x).astype(jnp.float32) * weigh))
        want = jax.tree.leaves(jax.grad(loss(ref), argnums=(0, 1))(params, x))
        for f in (new, old):
            assert rel(f(params, x), ref(params, x)) < 2e-2
            got = jax.tree.leaves(
                jax.grad(loss(f), argnums=(0, 1))(params, x))
            for g, w in zip(got, want):
                assert rel(g, w) < 4e-2
        assert rel(new(params, x), old(params, x)) < 2e-2


# -- the declared blobs ------------------------------------------------------

BLOBS = {
    "grouped": (
        "num_heads: 4 num_kv_heads: 2 head_dim: 16 qk_norm: true "
        "rope_theta: 10000.0 use_flash: true", 32,
        [("qkv_weight", (128, 32)), ("proj_weight", (32, 64)),
         ("qkv_bias", (128,)), ("proj_bias", (32,)), ("q_norm", (16,)),
         ("k_norm", (16,))]),
    "latent": (
        LAYER_CASES["latent_flash"][0], 64,
        [("q_a_weight", (48, 64)), ("q_norm", (48,)),
         ("q_b_weight", (96, 48)), ("kv_a_weight", (40, 64)),
         ("kv_norm", (32,)), ("kv_b_weight", (128, 32)),
         ("proj_weight", (64, 64))]),
}


class TestDeclaredBlobs:
    @pytest.mark.parametrize("form", list(BLOBS))
    def test_a_snapshot_from_before_the_change_loads(self, form, tmp_path):
        """Names, shapes and order of the blobs are what they were, so a
        .caffemodel written before PR 34 (positional blobs of these
        shapes) lands in the same tensors, and the layer computes from
        them what the old expressions compute."""
        text, c, blobs = BLOBS[form]
        net = Net(NetParameter.from_text("""
            layer { name: "in" type: "Input" top: "x"
                    input_param { shape { dim: 2 dim: 128 dim: %d } } }
            layer { name: "attn" type: "Attention" bottom: "x" top: "y"
                    attention_param { %s } }""" % (c, text)),
                  phase="TRAIN", precision="f32")
        layer = net.layers[-1]
        assert [(n, d.shape) for n, d in layer.params.items()] == blobs
        written = [np.asarray(0.2 * normal(20 + i, shape))
                   for i, (_, shape) in enumerate(blobs)]
        path = str(tmp_path / "before.caffemodel")
        save_caffemodel(path, {"attn": written}, "before")
        params, state = net.init(jax.random.PRNGKey(0))
        params, _ = net.import_weights(params, state, load_caffemodel(path))
        for (name, _), blob in zip(blobs, written):
            np.testing.assert_array_equal(np.asarray(params["attn"][name]),
                                          blob)
        x = normal(30, (2, 128, c))
        (y,), _ = layer.apply(params["attn"], {}, [x], train=True, rng=None)
        assert rel(y, old_apply(layer, params["attn"], x)) < 2e-6
