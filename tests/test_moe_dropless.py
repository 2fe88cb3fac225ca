"""The dropless expert layer's row bound (ops/moe.py `_row_bound`,
`_bounded_rows`): a layer that holds a share of the experts runs its
sorted buffer at 1.5 x the share's rows, and at every (token, choice)
pair whenever the held experts received that many or more (the bounded
buffer's last row has to be a dead one); with every expert held there is
one buffer and no `cond`.

The recipes' tiny sizes sit under one 512-row tile, where the bound is no
bound: these tests set the tile to 8 rows. 32 tokens, 2 choices of 8
experts, experts 4 and 5 held: 64 pairs, 16 expected here, a bound of 24.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffe_mpi_tpu.ops import moe as moe_ops

T, F, H, E, K, FIRST, HELD, BOUND = 32, 16, 8, 8, 2, 4, 2, 24
FLAVOURS = {
    "softmax-relu": dict(scoring="softmax", activation="relu"),
    "sigmoid-silu-shared": dict(scoring="sigmoid", activation="silu",
                                scale=2.5),
}
# tokens whose two choices are both held experts; the others choose two
# absent ones: 2 x tokens live rows
LIVE_TOKENS = {"under": 7, "one-under": 11, "exactly": 12, "one-over": 13,
               "every-pair": 32}


def layer(flavour: str, live_tokens: int):
    """(params, x, router_in): the router's product is the identity on the
    first E features, so `router_in` holds the logits themselves."""
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    params = {"gate": jnp.eye(F, E),
              "w1": jax.random.normal(ks[0], (HELD, F, H)) * 0.3,
              "w3": jax.random.normal(ks[1], (HELD, F, H)) * 0.3,
              "w2": jax.random.normal(ks[2], (HELD, H, F)) * 0.3}
    if "shared" in flavour:
        params.update(
            select_bias=jax.random.normal(ks[3], (E,)) * 0.01,
            shared_w1=jax.random.normal(ks[4], (F, H)) * 0.3,
            shared_w3=jax.random.normal(ks[5], (F, H)) * 0.3,
            shared_w2=jax.random.normal(ks[6], (H, F)) * 0.3)
    absent = jnp.array([[0, 1], [2, 3], [6, 7]])[jnp.arange(T) % 3]
    chosen = jnp.where((jnp.arange(T) < live_tokens)[:, None],
                       jnp.array([FIRST, FIRST + 1]), absent)
    logits = 4.0 * jnp.sum(jax.nn.one_hot(chosen, E), axis=1) \
        + 0.5 * jax.random.normal(ks[7], (T, E))
    router_in = jnp.pad(logits, ((0, 0), (0, F - E)))
    return params, jax.random.normal(ks[8], (T, F)), router_in


def run(flavour, live_tokens, tile, monkeypatch, poison_fallback=False):
    """y, rows and the gradients of every input under a 'tile'-row tile."""
    params, x, router_in = layer(flavour, live_tokens)
    monkeypatch.setattr(moe_ops, "ROW_TILE", tile)
    if poison_fallback:
        monkeypatch.setattr(moe_ops, "_fallback",
                            lambda *a: jnp.full((T, F), jnp.nan))

    def loss(params, x, router_in):
        y, rows = moe_ops.moe_dropless(
            params, x, router_in, top_k=K, first_expert=FIRST,
            **FLAVOURS[flavour])
        return jnp.sum(y * jnp.cos(jnp.arange(F))), (y, rows)
    (_, (y, rows)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(params, x, router_in)
    return {"y": y, "rows": rows, "x": grads[1], "router_in": grads[2],
            **{k: g for k, g in grads[0].items() if k != "select_bias"}}


@pytest.fixture(scope="module", params=[
    (f, n) for f in FLAVOURS for n in LIVE_TOKENS],
    ids=lambda p: f"{p[0]}-{p[1]}")
def both(request):
    """The layer under the bound (tile 8) and as it was (one tile holds
    every pair), on the same inputs."""
    flavour, name = request.param
    with pytest.MonkeyPatch.context() as mp:
        bounded = run(flavour, LIVE_TOKENS[name], 8, mp)
    with pytest.MonkeyPatch.context() as mp:
        full = run(flavour, LIVE_TOKENS[name], moe_ops.ROW_TILE, mp)
    return name, bounded, full


@pytest.mark.parametrize("what", ["y", "x", "w1", "w3", "w2", "gate",
                                  "router_in"])
def test_the_bound_changes_no_output_and_no_gradient(both, what):
    """Live rows under the bound, exactly at it and over it (the router
    forced onto the held experts, up to every pair): the same products on
    the same rows, so equal to float32 summation order; nothing dropped."""
    name, bounded, full = both
    assert float(jnp.sum(full["rows"])) == 2 * LIVE_TOKENS[name]
    np.testing.assert_array_equal(bounded["rows"], full["rows"])
    assert float(jnp.max(jnp.abs(full[what]))) > 0
    np.testing.assert_allclose(bounded[what], full[what], rtol=2e-6,
                               atol=1e-6)


def test_the_bound_is_one_and_a_half_shares_in_whole_tiles(monkeypatch):
    assert moe_ops._row_bound(6 * 8192, 16, 64) == 18432
    assert moe_ops._row_bound(8 * 8192, 16, 256) == 6144
    assert moe_ops._row_bound(8 * 8192, 256, 256) == 8 * 8192   # every pair
    assert moe_ops._row_bound(64, 2, 8) == 64       # one tile holds them all
    monkeypatch.setattr(moe_ops, "ROW_TILE", 8)
    assert moe_ops._row_bound(T * K, HELD, E) == BOUND
    assert moe_ops._row_bound(T * K, 3, E) == 40    # 36 rounds up to a tile


@pytest.mark.parametrize("flavour", FLAVOURS)
@pytest.mark.parametrize("name,falls_back", [
    ("under", False), ("one-under", False), ("exactly", True),
    ("one-over", True), ("every-pair", True)])
def test_the_fallback_runs_from_the_bound_up(flavour, name, falls_back,
                                               monkeypatch):
    """A fallback that returns NaN: the forward pass shows which branch
    the device took."""
    got = run(flavour, LIVE_TOKENS[name], 8, monkeypatch,
              poison_fallback=True)
    assert bool(jnp.all(jnp.isnan(got["y"]))) == falls_back
    assert bool(jnp.all(jnp.isfinite(got["y"]))) != falls_back


def conditionals(flavour, tile, monkeypatch, experts=E) -> int:
    """`conditional`s of the layer's compiled gradient (the lowered text
    also holds `platform_dependent`'s, on a constant)."""
    params, x, router_in = layer(flavour, 7)
    params.update(gate=jnp.eye(F, experts))
    if "select_bias" in params:
        params.update(select_bias=params["select_bias"][:experts])
    monkeypatch.setattr(moe_ops, "ROW_TILE", tile)
    first = FIRST if experts == E else 0
    return jax.jit(jax.grad(lambda p, x: jnp.sum(moe_ops.moe_dropless(
        p, x, router_in, top_k=K, first_expert=first,
        **FLAVOURS[flavour])[0]), (0, 1))).lower(
            params, x).compile().as_text().count(" conditional(")


@pytest.mark.parametrize("flavour", FLAVOURS)
def test_a_share_branches_once_each_way_and_a_whole_bank_never(
        flavour, monkeypatch):
    """Experts 4 and 5 of 8: one `cond` forward, one backward. Every expert
    held, or a buffer of one tile: the program as it was, no `cond`."""
    assert conditionals(flavour, 8, monkeypatch) == 2
    assert conditionals(flavour, 512, monkeypatch) == 0
    assert conditionals(flavour, 8, monkeypatch, experts=HELD) == 0


@pytest.mark.parametrize("flavour", FLAVOURS)
@pytest.mark.parametrize("name", ["under", "exactly", "one-over"])
def test_unwritten_rows_never_reach_a_result(flavour, name, monkeypatch):
    """On the chip a grouped product leaves the rows past its last group
    unwritten, in all three products: in the bounded buffer those are the
    rows between the live count and the bound. NaN there, forward and
    backward, must change nothing."""
    @jax.custom_vjp
    def poisoned(rows, bank, sizes):
        return fwd(rows, bank, sizes)[0]

    def dead(rows, sizes):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

    def fwd(rows, bank, sizes):
        out = jax.lax.ragged_dot(rows, bank, sizes)
        return jnp.where(dead(out, sizes), jnp.nan, out), (rows, bank, sizes)

    def bwd(res, g):
        rows, bank, sizes = res
        d_rows, d_bank = jax.vjp(
            lambda r, b: jax.lax.ragged_dot(r, b, sizes), rows, bank
        )[1](jnp.where(dead(g, sizes), 0.0, g))
        return jnp.where(dead(d_rows, sizes), jnp.nan, d_rows), d_bank, None
    poisoned.defvjp(fwd, bwd)

    with pytest.MonkeyPatch.context() as mp:
        want = run(flavour, LIVE_TOKENS[name], 8, mp)
    monkeypatch.setattr(moe_ops, "grouped_dot", poisoned)
    monkeypatch.setattr(moe_ops, "grouped_dot_t",
                        lambda *operands: bwd(operands[:3], operands[3])[:2])
    got = run(flavour, LIVE_TOKENS[name], 8, monkeypatch)
    for key in want:
        assert bool(jnp.all(jnp.isfinite(got[key]))), key
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5, atol=1e-6)
