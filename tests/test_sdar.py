"""SDAR-30B-A3B-Chat's block-diffusion training step through prototxt ->
Net, at a tiny size on the CPU, against the benchmark's plain reference
(benchmarks/reference/sdar_ref.py): D 64, 4 query heads over 2 key/value
heads of 16 with per-head q/k norm, one clean sequence of 32 in blocks of 4
(64 rows a layer), 3 blocks of 32 experts (2 held, 4 a token, width 32),
vocabulary 64 with mask id 63 — the sizes of
`models/sdar_30b_a3b/tiny_train_val.prototxt`, which the same generator
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

from reference import sdar_ref  # noqa: E402

from caffe_mpi_tpu.layers.sequence import block_diffusion_noise  # noqa: E402
from caffe_mpi_tpu.net import Net  # noqa: E402
from caffe_mpi_tpu.ops.attention import attention, rope  # noqa: E402
from caffe_mpi_tpu.ops.flash_attention import (  # noqa: E402
    _pad_len, _tile, flash_attention, tile_counts)
from caffe_mpi_tpu.proto import NetParameter  # noqa: E402

CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "sdar_30b_a3b.json")))
SZ = sdar_ref.sizes_from_config(CONFIG, CONFIG["rehearse"])
TINY = os.path.join(ROOT, "models", "sdar_30b_a3b",
                    "tiny_train_val.prototxt")
L, B = 32, SZ.block_length


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def net_from(text: str, batch: int = 1) -> Net:
    npar = NetParameter.from_text(text)
    for lp in npar.layer:
        if lp.type == "Input":
            for shape in lp.input_param.shape:
                shape.dim[0] = batch
    return Net(npar, phase="TRAIN", precision="f32")


def element_mask(half: int, block: int, padded: int = 0) -> np.ndarray:
    """The definition, case by case, element by element, over 2 x half
    positions or, padded, over more: positions past the two halves count
    as clean ones (the kernels' rule for padding; what they compute there
    is sliced off)."""
    i = np.arange(max(2 * half, padded))
    noisy = i < half
    blk = np.where(noisy, i, i - half) // block
    r, c = np.meshgrid(i, i, indexing="ij")
    return ((noisy[r] & noisy[c] & (blk[r] == blk[c]))
            | (noisy[r] & ~noisy[c] & (blk[c] < blk[r]))
            | (~noisy[r] & ~noisy[c] & (blk[c] <= blk[r])))


# -- the mask in the flash kernels -------------------------------------------

@pytest.fixture(scope="module", params=[(320, 1), (320, 4), (320, 32),
                                        (200, 4), (200, 32), (72, 1),
                                        (130, 96)],
                ids=lambda p: f"L{p[0]}_B{p[1]}")
def flash_pair(request):
    """(dense, jnp, flash): out and the three gradients under the block
    mask with grouped heads, by an explicit element mask, by the jnp path
    and through the interpreted kernels. 2 L = 640 walks five tiles of 128
    with the halves' border inside the third; 400 pads to one tile of 512;
    144 pads to 256; 260 pads to three tiles of 128, the last all padding
    (in blocks of 96: the clean rows a padded key tile's dK/dV reads)."""
    half, block = request.param
    s = 2 * half
    ks = jax.random.split(jax.random.PRNGKey(half + block), 4)
    q, do = (jax.random.normal(key, (1, s, 4, 32)) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, s, 2, 32)) for key in ks[2:])
    mask = jnp.asarray(element_mask(half, block))

    def dense(q, k, v):
        k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(do))
    path = lambda flash: lambda q, k, v: attention(
        q, k, v, block_diffusion=block, use_flash=flash)
    return run(dense), run(path(False)), run(path(True))


@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_flash_under_the_block_mask_is_the_jnp_path(flash_pair, which):
    """Forward, dQ and dK/dV kernels share the one definition; the jnp
    path is the definition element by element."""
    dense, plain, flash = flash_pair
    np.testing.assert_allclose(plain[which], dense[which], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(flash[which], plain[which], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("half,block", [
    (1024, 1), (1024, 4), (1024, 32), (1024, 512), (1024, 1024), (320, 4),
    (320, 32), (200, 4), (576, 64), (640, 256), (640, 96), (300, 7),
    (130, 96), (384, 384)])
def test_tile_counts_under_the_block_mask_match_the_element_mask(half,
                                                                 block):
    """(visited, inside) from the range code the kernels run against a
    count over the mask itself: a tile is visited iff it holds a valid
    score and inside iff it holds no other; padded keys cut a tile too."""
    s = 2 * half
    padded = _pad_len(s, 128)
    t = _tile(padded)
    mask = element_mask(half, block, padded)

    def brute(mask):
        tiles = mask.reshape(padded // t, t, padded // t, t)
        return int(tiles.any((1, 3)).sum()), int(tiles.all((1, 3)).sum())
    # the dK/dV kernel sees no padding; forward and dQ mask padded keys
    assert tile_counts(padded, padded, False, bd=(half, block), dkv=True) \
        == brute(mask)
    assert tile_counts(padded, padded, False, 0, s if padded != s else None,
                       bd=(half, block)) \
        == brute(mask & (np.arange(padded) < s)[None, :])


def test_tile_counts_at_the_benchmark_s_shape():
    """2 x 8,192 rows in tiles of 512: of 1,024 tiles a head 288 hold a
    live score, 240 of them whole; the clean-on-noisy quadrant, the
    off-diagonal tiles of noisy-on-noisy and the upper triangles of the
    other two are not visited. The same for the dK/dV kernel."""
    assert tile_counts(16384, 16384, False, bd=(8192, 4)) == (288, 240)
    assert tile_counts(16384, 16384, False, bd=(8192, 4), dkv=True) \
        == (288, 240)
    # the band's counts stand as they were
    assert tile_counts(8192, 8192, True) == (136, 120)
    assert tile_counts(8192, 8192, True, 4096) == (108, 84)


@pytest.fixture(scope="module", params=[(512, 4), (384, 192), (200, 32),
                                        (72, 1)],
                ids=lambda p: f"L{p[0]}_B{p[1]}")
def noisy_queries(request):
    """Queries of the noisy half against the whole sequence's keys: out
    and the three gradients by the dense element mask's first `half` rows,
    through the interpreted kernels at Sq = Sk / 2, and through them at
    full length with a zero cotangent on the clean rows; the forward
    kernel's lse at both lengths. 512 of 1,024 keys: tiles of 512 cut by
    blocks of 4; 384 of 768: query tiles of 128 inside blocks of 192, key
    tiles of 256; 200 of 400 and 72 of 144 pad both lengths."""
    from caffe_mpi_tpu.ops.flash_attention import _fwd_impl
    half, block = request.param
    s = 2 * half
    ks = jax.random.split(jax.random.PRNGKey(half + block), 5)
    q_all, do = (jax.random.normal(key, (1, s, 4, 32)) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, s, 2, 32)) for key in ks[2:4])
    q, do = q_all[:, :half], do[:, :half]
    mask = jnp.asarray(element_mask(half, block))[:half]

    def dense(q, k, v):
        k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    flash = lambda q, k, v: attention(q, k, v, block_diffusion=block,
                                      use_flash=True)
    out, vjp = jax.vjp(flash, q_all, k, v)
    dq, dk, dv = vjp(jnp.concatenate([do, jnp.zeros_like(do)], axis=1))
    runs = {"whole": (out[:, :half], dq[:, :half], dk, dv)}
    for name, fn in (("dense", dense), ("half", flash)):
        out, vjp = jax.vjp(fn, q, k, v)
        runs[name] = (out, *vjp(do))
    heads = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, t.shape[1], 32)
    if half % 128 == 0:
        runs["lse"] = tuple(_fwd_impl(heads(rows), heads(k), heads(v), False,
                                      None, bd=(half, block))[1]
                            for rows in (q, q_all))
    return runs


@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_noisy_queries_are_the_whole_sequence_s_noisy_rows(noisy_queries,
                                                           which):
    """The kernels at Sq = Sk / 2 give the full-length kernels' noisy rows,
    whose clean rows add nothing to dK and dV under a zero cotangent, and
    the dense mask's rows; the forward's lse too."""
    runs = noisy_queries
    np.testing.assert_allclose(runs["half"][which], runs["whole"][which],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(runs["half"][which], runs["dense"][which],
                               rtol=2e-4, atol=2e-5)
    if "lse" in runs:
        half, whole = runs["lse"]
        np.testing.assert_allclose(half, whole[..., :half.shape[-1]],
                                   rtol=1e-6, atol=1e-6)


def tile_sets(sq: int, sk: int, half: int, block: int, dkv: bool):
    """[(visited, inside)] a grid tile, the loop tiles each run of the
    block-diffusion mask's (`_runs`, what the kernels walk) puts in each,
    at the padded lengths sq x sk; and the same from the element mask's
    tiles that hold a live score and those that hold no other (keys past
    2 x half masked where queries are on the grid)."""
    from caffe_mpi_tpu.ops.flash_attention import _runs
    tq, tk = _tile(sq), _tile(sk)
    mask = element_mask(half, block, max(sq, sk))[:sq, :sk]
    if dkv:
        shape, tiles = (tk, tq, sq // tq, sq // tq), mask.T.reshape(
            sk // tk, tk, sq // tq, tq)
    else:
        live = 2 * half
        mask = mask & (np.arange(sk) < live)[None, :]
        shape = (tq, tk, -(-live // tk), live // tk)
        tiles = mask.reshape(sq // tq, tq, sk // tk, tk)
    got, want = [], []
    for g in range(tiles.shape[0]):
        runs = _runs(jnp.int32(g), *shape, (None, None), (half, block), dkv)
        span = lambda cut: {t for a, b, c in runs if cut is None or c == cut
                            for t in range(int(a), int(b))}
        got.append((span(None), span(False)))
        want.append(tuple(set(np.nonzero(how(tiles[g], (0, 2)))[0])
                          for how in (np.any, np.all)))
    return got, want


@pytest.mark.parametrize("full", [False, True], ids=["half", "whole"])
@pytest.mark.parametrize("half,block", [
    (1024, 4), (1024, 512), (320, 32), (300, 7), (130, 96), (384, 128),
    (384, 192), (384, 384), (400, 256), (520, 300), (640, 384), (72, 1)])
@pytest.mark.parametrize("dkv", [False, True], ids=["fwd_dq", "dkv"])
def test_every_live_tile_is_visited_and_no_cut_one_runs_unmasked(
        half, block, full, dkv):
    """Queries of the noisy half or the whole sequence, lengths padded
    each on its own and tiled each by its own length: a grid tile's runs
    visit exactly the tiles that hold a live score, and run without the
    mask only tiles that hold no other. (Where a key tile straddles the
    halves' border, or a block boundary falls inside a tile of the other
    length, a tile that is whole may still run cut: slower, never wrong.)
    Before the repair an empty span hid a tile from the next one: the
    dK/dV kernel skipped clean rows at 130 x 96 and 384 x 384."""
    sq = _pad_len(2 * half if full else half, 128)
    got, want = tile_sets(sq, _pad_len(2 * half, 128), half, block, dkv)
    for (visited, inside), (live, whole) in zip(got, want):
        assert visited == live and inside <= whole


@pytest.mark.parametrize("half,block", [
    (1024, 1), (1024, 4), (1024, 512), (320, 4), (320, 32), (200, 4),
    (130, 96), (384, 384), (300, 7), (72, 1)])
def test_tile_counts_of_noisy_queries_match_the_element_mask(half, block):
    """(visited, inside) at Sq = Sk / 2 against a count over the mask's
    first rows, where no tile that is whole runs cut; padded query rows
    count as clean ones."""
    sq, sk = _pad_len(half, 128), _pad_len(2 * half, 128)
    for dkv in (False, True):
        want = tile_sets(sq, sk, half, block, dkv)[1]
        assert tile_counts(sq, sk, False, 0,
                           2 * half if sk != 2 * half else None,
                           dkv=dkv, bd=(half, block)) \
            == tuple(sum(len(w[i]) for w in want) for i in (0, 1))


def test_tile_counts_of_noisy_queries_at_the_benchmark_s_shape():
    """8,192 noisy queries against 16,384 keys in tiles of 512: 152 of the
    288 tiles a head the whole sequence's queries visit, the 120 inside ones
    all among them; the 136 that go are the clean queries' own. The same
    for the dK/dV kernel."""
    assert tile_counts(8192, 16384, False, bd=(8192, 4)) == (152, 120)
    assert tile_counts(8192, 16384, False, bd=(8192, 4), dkv=True) \
        == (152, 120)


@pytest.mark.parametrize("how,match", [
    (dict(block_diffusion=4, causal=True), "neither"),
    (dict(block_diffusion=4, causal=True, window=8), "neither"),
    (dict(block_diffusion=-1), "block diffusion")])
@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_the_op_refuses_block_diffusion_with_a_band(how, match, flash):
    x = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match=match):
        attention(x, x, x, use_flash=flash, **how)
    with pytest.raises(ValueError, match="even length"):
        attention(x[:, :15], x[:, :15], x[:, :15], block_diffusion=4,
                  use_flash=flash)
    # queries are the whole sequence or its noisy half, nothing else
    with pytest.raises(ValueError, match="noisy half"):
        attention(x[:, :6], x, x, block_diffusion=4, use_flash=flash)


# -- the Attention layer ------------------------------------------------------

ATTN = """
    layer { name: "in" type: "Input" top: "x"
            input_param { shape { dim: 1 dim: %d dim: 64 } } }
    layer { name: "a" type: "Attention" bottom: "x" top: "y"
            attention_param { num_heads: 4 bias_term: false %s } }"""


@pytest.mark.parametrize("text,match", [
    ("block_diffusion: 4 causal: true", "neither causal"),
    ("block_diffusion: 4 causal: true window: 8", "neither causal"),
    ("block_diffusion: 4 sequence_parallel: true", "ring path"),
    ("block_diffusion: -4", "no block length"),
    ("block_diffusion: 4 kv_lora_rank: 8 q_lora_rank: 8 qk_nope_head_dim: 8 "
     "qk_rope_head_dim: 8 v_head_dim: 8 rope_theta: 1e4",
     "neither block_diffusion nor qk_norm"),
    ("qk_norm: true kv_lora_rank: 8 q_lora_rank: 8 qk_nope_head_dim: 8 "
     "qk_rope_head_dim: 8 v_head_dim: 8 rope_theta: 1e4",
     "neither block_diffusion nor qk_norm")])
def test_the_layer_refuses_what_block_diffusion_has_no_meaning_with(text,
                                                                    match):
    """`window` with block diffusion, and block diffusion with the latent
    or ring paths, raise a ValueError that says so; netlint's rule says
    the same (one spelling, proto/netshape.py)."""
    from caffe_mpi_tpu.proto.netshape import analyze_net
    with pytest.raises(ValueError, match=match):
        net_from(ATTN % (64, text))
    problems = analyze_net(NetParameter.from_text(ATTN % (64, text)),
                           phase="TRAIN").problems
    assert any(match in p.message for p in problems), problems


def test_an_odd_sequence_has_no_two_halves():
    with pytest.raises(ValueError, match="two equal halves"):
        net_from(ATTN % (63, "block_diffusion: 4"))


def test_rotary_positions_repeat_in_the_second_half():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    turned = rope(x, 1e4, period=8)
    np.testing.assert_array_equal(turned[:, :8], rope(x[:, :8], 1e4))
    np.testing.assert_array_equal(turned[:, 8:], rope(x[:, 8:], 1e4))
    np.testing.assert_array_equal(rope(x, 1e4, period=0), rope(x, 1e4))


@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_the_layer_is_the_reference_s_attention(flash):
    """Grouped heads, q/k norm with scales that are not one, rotary at
    i mod L, the block mask: equations 1-4 of the reference."""
    net = net_from(ATTN % (64, "num_kv_heads: 2 head_dim: 16 qk_norm: true "
                               "rope_theta: 1e6 block_diffusion: 4 "
                               + ("use_flash: true" if flash else "")))
    params, state = net.init(jax.random.PRNGKey(2))
    p = dict(params["a"])
    assert p["q_norm"].shape == p["k_norm"].shape == (16,)
    p["q_norm"], p["k_norm"] = (1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(i), (16,)) for i in (3, 4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 64))
    blobs, _, _ = net.apply({"a": p}, state, {"x": x}, train=True,
                            rng=jax.random.PRNGKey(0))
    qkv = p["qkv_weight"]
    lp = {"wq": qkv[:64].T, "wk": qkv[64:96].T, "wv": qkv[96:].T,
          "wo": p["proj_weight"].T, "gq": p["q_norm"], "gk": p["k_norm"]}
    with jax.default_matmul_precision("highest"):
        want = sdar_ref.attention(lp, x, SZ, 16, None)
        no_norm = sdar_ref.attention(lp, x, SZ, 16, None, qk_norm=False)
        absolute = sdar_ref.attention(lp, x, SZ, 16, None,
                                      positions="absolute")
    assert rel(blobs["y"], want) < 1e-5
    assert rel(no_norm, want) > 0.05 and rel(absolute, want) > 0.05


NOISY = """
    layer { name: "in" type: "Input" top: "x"
            input_param { shape { dim: 1 dim: 64 dim: 64 } } }
    layer { name: "half" type: "Slice" bottom: "x" top: "noisy" top: "clean"
            slice_param { axis: 1 slice_point: 32 } }
    layer { name: "drop" type: "Silence" bottom: "clean" }
    layer { name: "a" type: "Attention" %s top: "y"
            attention_param { num_heads: 4 bias_term: false %s } }"""
BLOCK_ATTN = ("num_kv_heads: 2 head_dim: 16 qk_norm: true rope_theta: 1e6 "
              "block_diffusion: 4 ")


@pytest.mark.parametrize("flash", [False, True], ids=["jnp", "flash"])
def test_noisy_queries_are_the_first_half_of_the_layer(flash):
    """Bottom 0 the noisy half's rows, bottom 1 the whole sequence's: the
    output, and the gradients of the weights and of the input through both
    bottoms, are the one-bottom layer's on the first half of the rows
    (under a cotangent that is zero on the clean half); the layer declares
    the same blobs."""
    how = BLOCK_ATTN + ("use_flash: true" if flash else "")
    whole = net_from(NOISY % ('bottom: "x"', how))
    noisy = net_from(NOISY % ('bottom: "noisy" bottom: "x"', how))
    params, state = whole.init(jax.random.PRNGKey(2))
    p = dict(params["a"])
    assert {k: v.shape for k, v in noisy.init(jax.random.PRNGKey(2))[0][
        "a"].items()} == {k: v.shape for k, v in p.items()}
    p["q_norm"], p["k_norm"] = (1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(i), (16,)) for i in (3, 4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 64))
    cot = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 64))

    def run(net, rows):
        def loss(p, x):
            y = net.apply({"a": p}, state, {"x": x}, train=True,
                          rng=jax.random.PRNGKey(0))[0]["y"][:, :rows]
            return jnp.sum(y * cot), y
        (_, y), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
        return y, grads
    with jax.default_matmul_precision("highest"):
        want, (want_p, want_x) = run(whole, 32)
        got, (got_p, got_x) = run(noisy, 64)
    assert got.shape == (1, 32, 64)
    assert rel(got, want) < 1e-5 and rel(got_x, want_x) < 1e-5
    for name in p:
        assert rel(got_p[name], want_p[name]) < 1e-5, name


@pytest.mark.parametrize("bottoms,how,match", [
    ('bottom: "noisy" bottom: "x"', "num_kv_heads: 2", "a second bottom"),
    ('bottom: "noisy" bottom: "x"', "block_diffusion: 4 kv_lora_rank: 8 "
     "q_lora_rank: 8 qk_nope_head_dim: 8 qk_rope_head_dim: 8 v_head_dim: 8 "
     "rope_theta: 1e4", "a second bottom"),
    ('bottom: "x" bottom: "x"', "block_diffusion: 4", "noisy half"),
    ('bottom: "noisy" bottom: "noisy"', "block_diffusion: 4", "noisy half")])
def test_the_layer_refuses_other_key_value_rows(bottoms, how, match):
    """A second bottom is the key/value rows of the block mask's sequence,
    twice the query rows, in the grouped form; netlint's rule says the
    same (one spelling, proto/netshape.py)."""
    from caffe_mpi_tpu.proto.netshape import analyze_net
    text = NOISY % (bottoms, how)
    with pytest.raises(ValueError, match=match):
        net_from(text)
    problems = analyze_net(NetParameter.from_text(text),
                           phase="TRAIN").problems
    assert any(match in p.message for p in problems), problems


def test_noisy_queries_drop_their_products_from_the_mac_model():
    """The MAC model (`netshape.macs_per_image`) of the two-bottom form:
    the one-bottom layer's less q's and the output's products over the
    clean half's 32 rows and less the clean rows' half of the pairs the
    mask leaves, 32 x (32 + 4) a head of 2 x 16 lanes."""
    from caffe_mpi_tpu.proto.netshape import analyze_net, layer_macs
    macs = lambda bottoms: next(
        layer_macs(info) for info in analyze_net(NetParameter.from_text(
            NOISY % (bottoms, BLOCK_ATTN)), phase="TRAIN").layers
        if info.name == "a")
    whole, noisy = macs('bottom: "x"'), macs('bottom: "noisy" bottom: "x"')
    assert whole - noisy == 32 * 64 * 2 * 4 * 16 + 4 * 16 * 32 * (32 + 4)


# -- the noise layer ----------------------------------------------------------

class TestNoise:
    ARGS = dict(block_length=4, mask_id=63, t_min=1e-3, ignore_label=-1)

    def test_same_key_same_draw_another_key_another(self):
        x0 = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 63)
        a = block_diffusion_noise(jax.random.PRNGKey(1), x0, **self.ARGS)
        b = block_diffusion_noise(jax.random.PRNGKey(1), x0, **self.ARGS)
        c = block_diffusion_noise(jax.random.PRNGKey(2), x0, **self.ARGS)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert not np.array_equal(a[1], c[1])

    @pytest.fixture(scope="class")
    def draw(self):
        x0 = jax.random.randint(jax.random.PRNGKey(3), (1, 8192), 0, 63)
        return x0, block_diffusion_noise(jax.random.PRNGKey(4), x0,
                                         **self.ARGS)

    def test_the_masked_share_is_a_half_within_three_sigma(self, draw):
        """E[t] = 1/2; over 2,048 blocks of 4 the share's deviation is
        sqrt((1/12 + (1/6) / 4) / 2048) = 0.0078."""
        x0, (ids, labels, weights, masked) = draw
        share = float(masked) / x0.size
        assert abs(share - 0.5) < 3 * 0.0078
        assert float(masked) == int(np.sum(np.asarray(labels) != -1))

    def test_mask_id_only_where_masked_and_weights_are_one_over_t(self,
                                                                  draw):
        x0, (ids, labels, weights, _) = draw
        ids, labels, weights, x0 = (np.asarray(a) for a in
                                    (ids, labels, weights, x0))
        masked = labels != -1
        assert ids.shape == (1, 16384) and ids.dtype == np.int32
        np.testing.assert_array_equal(ids[:, 8192:], x0)
        np.testing.assert_array_equal(ids[:, :8192],
                                      np.where(masked, 63, x0))
        np.testing.assert_array_equal(labels[masked], x0[masked])
        assert (weights[~masked] == 0).all()
        # one t a block: its masked positions share one weight in
        # [1, 1 / t_min]
        w = weights.reshape(-1, 4)
        m = masked.reshape(-1, 4)
        top = w.max(axis=1, keepdims=True)
        assert (w[m] == np.broadcast_to(top, w.shape)[m]).all()
        assert (w[m] >= 1).all() and (w[m] <= 1000 * (1 + 1e-6)).all()
        # the benchmark's own account of the definition agrees
        faults = sdar_ref.noise_faults(
            jnp.asarray(x0), jnp.asarray(ids), jnp.asarray(labels),
            jnp.asarray(weights),
            dataclasses.replace(SZ, block_length=4, mask_id=63))
        assert not any(v for k, v in faults.items() if k != "masked_share")

    def test_the_layer_draws_from_its_rng_and_counts(self):
        net = net_from("""
            layer { name: "in" type: "Input" top: "tokens"
                    input_param { shape { dim: 2 dim: 30 } } }
            layer { name: "noise" type: "BlockDiffusionNoise"
                    bottom: "tokens" top: "ids" top: "label" top: "weight"
                    top: "masked"
                    block_diffusion_param { block_length: 4 mask_id: 63 } }
            """, batch=2)
        x0 = jax.random.randint(jax.random.PRNGKey(5), (2, 30), 0, 63)
        run = lambda key: net.apply({}, {}, {"tokens": x0}, train=True,
                                    rng=key)[0]
        a, b, c = (run(jax.random.PRNGKey(k)) for k in (0, 0, 1))
        assert a["ids"].shape == (2, 60) and a["masked"].shape == ()
        np.testing.assert_array_equal(a["ids"], b["ids"])
        assert not np.array_equal(a["label"], c["label"])
        # 30 is no multiple of 4: the last block holds two positions
        last = np.asarray(a["weight"])[:, 28:]
        assert ((last == 0) | (last == last.max(axis=1, keepdims=True))).all()

    @pytest.mark.parametrize("text,match", [
        ("block_length: 0", "block_length"), ("t_min: 0.0", "t_min"),
        ("t_min: 1.5", "t_min")])
    def test_bad_parameters_are_refused(self, text, match):
        with pytest.raises(ValueError, match=match):
            net_from("""
                layer { name: "in" type: "Input" top: "tokens"
                        input_param { shape { dim: 1 dim: 8 } } }
                layer { name: "noise" type: "BlockDiffusionNoise"
                        bottom: "tokens" top: "a" top: "b" top: "c"
                        block_diffusion_param { %s } }""" % text)


# -- the loss's third bottom --------------------------------------------------

LOSS = """
    layer { name: "in" type: "Input" top: "x" top: "label" top: "w"
            input_param { shape { dim: 2 dim: 6 dim: 5 }
                          shape { dim: 2 dim: 6 } shape { dim: 2 dim: 6 } } }
    layer { name: "loss" type: "SoftmaxWithLoss" bottom: "x" bottom: "label"
            %s top: "loss" softmax_param { axis: 2 }
            loss_param { ignore_label: -1 normalization: %s } }"""


class TestWeightedLoss:
    X = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 5))
    LABEL = jnp.array([[0, 3, -1, 4, -1, 2], [1, -1, -1, 0, 2, 2]])
    W = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 6))) + 0.5

    def value_and_grad(self, weighted: bool, mode: str, w=None):
        net = net_from(LOSS % ('bottom: "w"' if weighted else "", mode),
                       batch=2)
        feeds = {"x": self.X, "label": self.LABEL,
                 "w": self.W if w is None else w}
        return jax.value_and_grad(lambda x: net.apply(
            {}, {}, {**feeds, "x": x}, train=True, rng=None)[2])(self.X)

    @pytest.mark.parametrize("mode,norm", [("FULL", 12.0), ("VALID", 8.0),
                                           ("BATCH_SIZE", 2.0),
                                           ("NONE", 1.0)])
    def test_against_a_hand_sum(self, mode, norm):
        got, grad = self.value_and_grad(True, mode)
        logp = np.asarray(jax.nn.log_softmax(self.X, axis=-1), np.float64)
        want = sum(-float(self.W[n, i]) * logp[n, i, int(self.LABEL[n, i])]
                   for n in range(2) for i in range(6)
                   if self.LABEL[n, i] != -1) / norm
        assert abs(float(got) - want) < 1e-5 * want
        # an ignored position gets no gradient, a counted one its weight's
        assert not np.asarray(grad)[0, 2].any()
        p = np.asarray(jax.nn.softmax(self.X, axis=-1))
        hot = np.eye(5)[0]
        np.testing.assert_allclose(
            grad[0, 0], float(self.W[0, 0]) * (p[0, 0] - hot) / norm,
            rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("mode", ["FULL", "VALID"])
    def test_two_bottoms_are_bit_for_bit_the_loss_without_weights(self,
                                                                  mode):
        """Weights of one are exact, so the third bottom's path and the
        two-bottom path agree to the bit, value and gradient; and the
        two-bottom path traces no multiply by a weight at all."""
        plain, plain_g = self.value_and_grad(False, mode)
        ones, ones_g = self.value_and_grad(True, mode,
                                           jnp.ones_like(self.W))
        assert float(plain) == float(ones)
        np.testing.assert_array_equal(plain_g, ones_g)
        from caffe_mpi_tpu.layers.losses import _softmax_nll
        count = lambda w: str(jax.make_jaxpr(jax.grad(
            lambda x: _softmax_nll(x, self.LABEL, w, 2, -1, mode)))(
                self.X)).count(" mul ")
        assert count(self.W) == count(None) + 2

    def test_weights_that_are_not_one_a_label_are_refused(self):
        with pytest.raises(ValueError, match="not one a label"):
            net_from(LOSS.replace("shape { dim: 2 dim: 6 } }",
                                  "shape { dim: 2 dim: 5 } }")
                     % ('bottom: "w"', "FULL"), batch=2)


# -- the shares ---------------------------------------------------------------

MOE = """type: "MoE" top: "rows" loss_weight: 0 loss_weight: 0
  moe_param { num_experts: 128 hidden_dim: 32 top_k: 8 dropless: true
    experts_held: %d first_expert: %d activation: "silu"
    weight_filler { type: "gaussian" std: 0.2 } }"""
X = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))


def moe_layer(held: int, first: int):
    return net_from("""
        layer { name: "in" type: "Input" top: "x"
                input_param { shape { dim: 2 dim: 32 dim: 64 } } }
        layer { name: "l" bottom: "x" bottom: "x" top: "y" %s }"""
                    % (MOE % (held, first)), batch=2)


def test_eight_shares_of_sixteen_add_up_to_the_uncut_layer():
    """THE share test at SDAR's routing (eight shares of 16 of 128
    experts, softmax top-8 renormalised, SiLU gates): what the shares give
    adds up to the uncut reference's whole layer, and every (token,
    choice) pair is counted once."""
    sz = dataclasses.replace(SZ, experts=128, experts_held=128, top_k=8)
    whole = moe_layer(128, 0)
    params, _ = whole.init(jax.random.PRNGKey(11))
    p = params["l"]
    lp = {"router": p["gate"], "gate": p["w1"], "up": p["w3"],
          "down": p["w2"]}
    with jax.default_matmul_precision("highest"):
        r = X @ lp["router"]
        want = sdar_ref.experts(lp, X, r, sz, None)
        parts = sum(sdar_ref.experts(
            {**lp, **{k: lp[k][16 * i:16 * i + 16]
                      for k in ("gate", "up", "down")}},
            X, r, sz, None, first_expert=16 * i, held=16) for i in range(8))
    assert rel(parts, want) < 1e-5
    blobs, _, _ = whole.apply(params, {}, {"x": X}, train=True,
                              rng=jax.random.PRNGKey(0))
    assert rel(blobs["y"], want) < 1e-5
    total, rows = jnp.zeros_like(X), 0.0
    for share in range(8):
        net = moe_layer(16, 16 * share)
        mine = {**p, **{k: p[k][16 * share:16 * share + 16]
                        for k in ("w1", "w2", "w3")}}
        blobs, _, _ = net.apply({"l": mine}, {}, {"x": X}, train=True,
                                rng=jax.random.PRNGKey(0))
        total = total + blobs["y"]
        rows += float(jnp.sum(blobs["rows"]))
    assert rows == 2 * 32 * 8
    assert rel(total, want) < 1e-5
    # the published routing (softmax over all, top 8, renormalise) is the
    # softmax over the 8 chosen logits
    idx, w = sdar_ref.route(r, 8)
    top, idx2 = jax.lax.top_k(r, 8)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_allclose(w, jax.nn.softmax(top, -1), rtol=1e-5)


def test_a_tiled_router_sends_every_row_once_to_each_share():
    """`gate_filler { tile: 8 }`: the router is eight copies of one matrix
    of 16 columns, so a row's eight largest logits are one column's eight
    copies: one expert in each share of 16, weight 1/8 each, and each share
    receives every row exactly once."""
    from caffe_mpi_tpu.core.fillers import fill
    from caffe_mpi_tpu.proto.config import FillerParameter
    gate = fill(FillerParameter(type="gaussian", std=0.02, tile=8),
                jax.random.PRNGKey(0), (64, 128))
    np.testing.assert_array_equal(gate[:, :16], gate[:, 112:])
    assert float(jnp.std(gate)) > 0.015
    with pytest.raises(ValueError, match="does not divide"):
        fill(FillerParameter(type="gaussian", tile=3), jax.random.PRNGKey(0),
             (64, 128))
    for share in (0, 5):
        net = net_from("""
            layer { name: "in" type: "Input" top: "x"
                    input_param { shape { dim: 2 dim: 32 dim: 64 } } }
            layer { name: "l" bottom: "x" bottom: "x" top: "y" %s }"""
                       % (MOE % (16, 16 * share)).replace(
                           "dropless: true", "dropless: true gate_filler "
                           '{ type: "gaussian" std: 0.02 tile: 8 }'),
                       batch=2)
        params, _ = net.init(jax.random.PRNGKey(11))
        blobs, _, _ = net.apply(params, {}, {"x": X}, train=True,
                                rng=jax.random.PRNGKey(0))
        assert float(jnp.sum(blobs["rows"])) == 2 * 32
        lp = {"router": params["l"]["gate"], "gate": params["l"]["w1"],
              "up": params["l"]["w3"], "down": params["l"]["w2"]}
        sz = dataclasses.replace(SZ, experts=128, experts_held=16, top_k=8,
                                 first_expert=16 * share)
        with jax.default_matmul_precision("highest"):
            idx, w = sdar_ref.route(X @ lp["router"], 8)
            want = sdar_ref.experts(lp, X, X @ lp["router"], sz, None)
        np.testing.assert_array_equal(np.sort(np.asarray(idx) // 16, -1),
                                      np.broadcast_to(np.arange(8),
                                                      idx.shape))
        np.testing.assert_allclose(w, 1 / 8, rtol=1e-6)
        assert rel(blobs["y"], want) < 1e-5


# -- the whole net ------------------------------------------------------------

def after_the_noise(net) -> int:
    return 1 + next(i for i, layer in enumerate(net.layers)
                    if layer.lp.type == "BlockDiffusionNoise")


def tiny_net(flash: bool) -> Net:
    text = open(TINY).read()
    if not flash:
        text = text.replace("use_flash: true", "use_flash: false")
    return net_from(text)


@pytest.fixture(scope="module")
def case():
    """Weights, clean tokens and the layer's draw (the same for both paths:
    neither depends on use_flash), and what the reference makes of them."""
    net = tiny_net(False)
    params, state = net.init(jax.random.PRNGKey(1))
    x0 = jax.random.randint(jax.random.PRNGKey(2), (1, L), 0, SZ.mask_id)
    rng = jax.random.PRNGKey(3)
    env, _, _ = net.apply_range(params, state, {"tokens": x0}, {}, 0,
                                after_the_noise(net), train=True, rng=rng)
    ref = sdar_ref.from_net(params, SZ)
    draw = (env["ids"], env["label"], env["weight"])
    loss, grads = jax.jit(jax.value_and_grad(lambda p: sdar_ref.loss(
        sdar_ref.from_net(p, SZ), *draw, SZ)))(params)
    return {"params": params, "state": state, "x0": x0, "rng": rng,
            "ref": ref, "ids": env["ids"], "loss": float(loss),
            "grads": grads,
            "logits": sdar_ref.forward(ref, env["ids"], SZ, q_block=16)}


@pytest.fixture(scope="module", params=[False, True], ids=["jnp", "flash"])
def whole_net(request, case):
    net = tiny_net(request.param)
    blobs, _, loss = net.apply(case["params"], case["state"],
                               {"tokens": case["x0"]}, train=True,
                               rng=case["rng"])
    np.testing.assert_array_equal(blobs["ids"], case["ids"])
    return net, blobs, loss


class TestWholeNet:
    def test_the_recipe_is_sdar_s_block(self, whole_net):
        net = whole_net[0]
        attn = next(l for l in net.layers if l.lp.name == "blk0/attn")
        p = attn.lp.attention_param
        assert (p.block_diffusion, p.qk_norm, p.causal, p.window) \
            == (4, True, False, 0)
        assert attn.lp.remat
        moe = next(l for l in net.layers if l.lp.name == "blk0/moe")
        assert (moe.p.scoring, moe.p.activation) == ("softmax", "silu")

    @pytest.mark.parametrize("what", ["logits", "loss"])
    def test_against_the_reference(self, whole_net, case, what):
        """Logits and loss to 1e-4 in f32 on seeded weights, on the
        layer's own draw."""
        _, blobs, loss = whole_net
        if what == "logits":
            want = case["logits"]
            assert want.shape == blobs["logits"].shape == (1, L, SZ.vocab)
            assert rel(blobs["logits"], want) < 1e-4
        else:
            assert abs(float(loss) - case["loss"]) < 1e-4 * case["loss"]
            assert float(blobs["masked"]) > 0

    def test_every_leaf_s_gradient_is_the_reference_s(self, whole_net, case):
        net = whole_net[0]
        got = jax.jit(jax.grad(lambda p: net.apply(
            p, case["state"], {"tokens": case["x0"]}, train=True,
            rng=case["rng"])[2]))(case["params"])
        frozen = {(layer, blob) for layer, blob, decl
                  in net.learnable_param_decls() if decl.lr_mult == 0.0}
        assert frozen == {(f"blk{l}/moe", "gate") for l in range(SZ.layers)}
        checked = 0
        for layer, blobs_ in case["grads"].items():
            for blob, w in blobs_.items():
                if (layer, blob) in frozen:
                    assert not np.asarray(got[layer][blob]).any()
                    continue
                assert rel(got[layer][blob], w) < 1e-4, (layer, blob)
                checked += 1
        assert checked == 9 * SZ.layers + 3

    def test_the_recipe_built_the_old_way_agrees(self, whole_net, case):
        """The last block over both halves and the noisy half sliced out
        after it, as the recipe was built before: the noisy half's logits,
        the loss and every leaf's gradient agree to rounding. The mask
        hides the clean rows from the noisy ones and nothing reads the
        clean rows' output, so leaving that output out changes nothing."""
        net = whole_net[0]
        old = Net(the_old_way(net.param), phase="TRAIN", precision="f32")
        apply = lambda net, p: net.apply(
            p, case["state"], {"tokens": case["x0"]}, train=True,
            rng=case["rng"])
        logits = lambda net: jax.jit(lambda p: apply(net, p)[0]["logits"])(
            case["params"])
        assert rel(logits(net), logits(old)) < 1e-5
        (loss, got), (want_loss, want) = (
            jax.jit(jax.value_and_grad(lambda p: apply(n, p)[2]))(
                case["params"]) for n in (net, old))
        assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
        assert got.keys() == want.keys()
        for layer, blobs_ in want.items():
            for blob, w in blobs_.items():
                if np.asarray(w).any():
                    assert rel(got[layer][blob], w) < 1e-5, (layer, blob)
                else:
                    assert not np.asarray(got[layer][blob]).any()

    def test_rows_count_every_pair_routed_here(self, whole_net):
        blobs = whole_net[1]
        for l in range(SZ.layers):
            rows = blobs[f"blk{l}/moe_rows"]
            assert rows.shape == (SZ.experts_held,)
            # the tiled router sends each of the 2 L rows here once; the
            # last block's expert layer sees the noisy half alone
            assert float(jnp.sum(rows)) == (L if l == SZ.layers - 1
                                            else 2 * L)


def the_old_way(npar: NetParameter) -> NetParameter:
    """The recipe as generated before its last block took the queries of
    the noisy half: that block's attention over both halves' rows, its
    residuals and expert layer on all of them, and the noisy half sliced
    out after it for the final norm."""
    import copy
    npar = copy.deepcopy(npar)
    b = SZ.layers - 1
    layers = {lp.name: lp for lp in npar.layer}
    below = layers[f"blk{b}/noisy"].bottom[0]
    gone = {f"blk{b}/noisy", f"blk{b}/ln1_noisy", f"blk{b}/drop_clean"}
    layers[f"blk{b}/attn"].bottom = [f"blk{b}/ln1"]
    layers[f"blk{b}/res1"].bottom[0] = below
    layers["ln_f"].bottom = ["noisy"]
    tail = NetParameter.from_text(f"""
        layer {{ name: "noisy" type: "Slice" bottom: "blk{b}/res2"
                top: "noisy" top: "clean"
                slice_param {{ axis: 1 slice_point: {L} }} }}
        layer {{ name: "drop_clean" type: "Silence" bottom: "clean" }}
        """).layer
    at = [lp.name for lp in npar.layer].index("ln_f")
    npar.layer = [lp for lp in npar.layer[:at] if lp.name not in gone] \
        + tail + npar.layer[at:]
    return npar


@jax.jit
def plain_block_causal(params, ids):
    """A plain pass over ONE sequence `ids` (1, M) in blocks of B with
    positions 0..M-1: a row sees the blocks up to and including its own
    (bidirectional inside a block, causal across blocks). The benchmark's
    layer equations with this mask; nothing of the vectorised form.
    Returns the last layer's output and its final norm."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["embed"], ids, axis=0)
        m = ids.shape[1]
        at = jnp.arange(m)
        seen = (at[None, :] // B) <= (at[:, None] // B)
        for lp in params["layers"]:
            a = sdar_ref.rms(h, lp["g1"], SZ.eps)
            q = (a @ lp["wq"]).reshape(1, m, SZ.heads, SZ.head_dim)
            k = (a @ lp["wk"]).reshape(1, m, SZ.kv_heads, SZ.head_dim)
            v = (a @ lp["wv"]).reshape(1, m, SZ.kv_heads, SZ.head_dim)
            q = sdar_ref.rotate(sdar_ref.rms(q, lp["gq"], SZ.eps),
                                SZ.rope_theta, at)
            k = sdar_ref.rotate(sdar_ref.rms(k, lp["gk"], SZ.eps),
                                SZ.rope_theta, at)
            group = SZ.heads // SZ.kv_heads
            k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
            s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(SZ.head_dim)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            o = jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(1, m, -1)
            u = h + o @ lp["wo"]
            mm = sdar_ref.rms(u, lp["g2"], SZ.eps)
            h = u + sdar_ref.experts(lp, mm, mm @ lp["router"], SZ, None)
        return h, sdar_ref.rms(h, params["g_f"], SZ.eps)


@pytest.fixture(scope="module")
def plain(case):
    """The plain net's final-normed output on block b of [x_0's blocks
    before b | x_t's block b], for every b, and its pass over x_0 alone
    through every block but the last.
    The prefixes run at one length, L, filled up with tokens after block b
    that a block-causal pass cannot see (asserted for one block, and block
    0 is also run alone, at its own length)."""
    x0, xt = case["x0"], case["ids"][:, :L]
    blocks = []
    for b in range(L // B):
        ids = jnp.concatenate([x0[:, :b * B], xt[:, b * B:(b + 1) * B],
                               jnp.full((1, L - (b + 1) * B), 5, x0.dtype)],
                              axis=1)
        blocks.append(plain_block_causal(case["ref"], ids)[1][
            :, b * B:(b + 1) * B])
    alone = plain_block_causal(case["ref"], xt[:, :B])[1]
    np.testing.assert_allclose(blocks[0], alone, rtol=1e-5, atol=1e-6)
    other = jnp.concatenate([x0[:, :2 * B], xt[:, 2 * B:3 * B],
                             x0[:, 3 * B:]], axis=1)
    np.testing.assert_array_equal(
        plain_block_causal(case["ref"], other)[1][:, 2 * B:3 * B], blocks[2])
    but_last = {**case["ref"], "layers": case["ref"]["layers"][:-1]}
    return blocks, plain_block_causal(but_last, x0)[0]


class TestTheDefinition:
    """What the vectorised form computes, said without it."""

    @pytest.mark.parametrize("b", range(L // B))
    def test_each_noisy_block_is_a_plain_pass_over_its_prefix(self,
                                                             whole_net,
                                                             plain, b):
        """For every block b the noisy half's output on block b is the
        plain net's on [x_0's blocks before b | x_t's block b] alone."""
        got = whole_net[1]["ln_f"][:, b * B:(b + 1) * B]
        assert rel(got, plain[0][b]) < 1e-4

    def test_the_clean_half_never_saw_the_noise(self, whole_net, plain):
        """The clean half's input to the last block, the last it goes
        through, is a block-causal pass over x_0 through the others."""
        last = SZ.layers - 1
        assert rel(whole_net[1][f"blk{last}/clean"], plain[1]) < 1e-4

    @pytest.mark.parametrize("b", [0, 3, 7])
    def test_what_a_block_cannot_see_leaves_it_bit_identical(self,
                                                             whole_net,
                                                             case, b):
        """Perturbing x_t outside block b, or x_0 from block b on, leaves
        block b's logits bit-identical; perturbing what it does see (its
        own noisy tokens, the clean blocks before it) does not."""
        net, blobs, _ = whole_net
        lo = after_the_noise(net)

        @jax.jit
        def logits(ids):
            env = {"ids": ids, "label": blobs["label"],
                   "weight": blobs["weight"]}
            out, _, _ = net.apply_range(case["params"], case["state"], {},
                                        env, lo, len(net.layers),
                                        train=True, rng=None)
            return out["logits"][0, b * B:(b + 1) * B]
        ids = blobs["ids"]
        at = jnp.arange(2 * L)
        mine = (at >= b * B) & (at < (b + 1) * B)           # x_t's block b
        before = (at >= L) & (at < L + b * B)               # x_0's blocks < b
        other = (ids + 1 + at % 5) % SZ.mask_id
        base = np.asarray(logits(ids))
        unseen = logits(jnp.where(mine | before, ids, other))
        np.testing.assert_array_equal(unseen, base)
        assert not np.array_equal(logits(jnp.where(mine, other, ids)), base)
        if b:
            assert not np.array_equal(
                logits(jnp.where(before, other, ids)), base)


def test_the_committed_recipes_are_what_the_generator_emits():
    import generate_models as g
    for net, sizes in (("train_val.prototxt", g.SDAR),
                       ("tiny_train_val.prototxt", g.SDAR_TINY)):
        text = g.sdar(**sizes, remat=g.SDAR_REMAT).to_prototxt()
        assert open(os.path.join(ROOT, "models", "sdar_30b_a3b",
                                 net)).read() == text + "\n"


def test_the_tiny_recipe_trains():
    """`caffe train`'s path: the Solver on the tiny recipe, fresh noise
    every step; the fixed batch's loss falls."""
    from caffe_mpi_tpu.proto import SolverParameter
    from caffe_mpi_tpu.solver import Solver
    sp = SolverParameter.from_file(os.path.join(
        ROOT, "models", "sdar_30b_a3b", "tiny_solver.prototxt"))
    sp.max_iter, sp.snapshot, sp.display = 40, 0, 0
    sp.snapshot_after_train = False
    sp.random_seed = 5
    solver = Solver(sp, model_dir=ROOT)
    x0 = jax.random.randint(jax.random.PRNGKey(6), (1, L), 0, SZ.mask_id)
    try:
        first = float(solver.step(1, lambda it: {"tokens": x0}))
        for _ in range(7):
            last = float(solver.step(5, lambda it: {"tokens": x0}))
    finally:
        solver.close()
    assert np.isfinite(last) and last < 0.8 * first
