"""The two gathers indexed by token ids, the `Embed` lookup and the label
pick of `SoftmaxWithLoss`, carry backward passes of their own
(layers/dense.py `_lookup`, layers/losses.py `_softmax_nll`): jax's default
transpose of a gather is a zero-fill and a scatter, which XLA:TPU runs at a
few per cent of the chip's memory speed (PERF.md section 6, PR 28).

Three things are held here: each backward pass equals jax's own on the
plain formulation (kept in this file as the oracle), the `Embed` layer
falls back to jax's rule past its size limit with the same gradient, and
the tiny SmallThinker recipe's train step lowers without a row scatter
under either layer and without a float32 zero buffer of the logits' shape.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffe_mpi_tpu.core.types import DtypePolicy
from caffe_mpi_tpu.layers import dense
from caffe_mpi_tpu.utils import spans
from gradcheck import make_layer

ROOT = Path(__file__).resolve().parent.parent
VOCAB, WIDTH = 23, 8
F32 = jnp.float32


def f32(x):
    return np.asarray(x.astype(F32))


# -- Embed -------------------------------------------------------------------

EMBED_IDS = {
    "all-equal": np.full((12,), 5),
    "all-distinct": np.random.RandomState(0).permutation(VOCAB)[:12],
    "some-absent": np.array([0, 3, 3, 22, 7, 3, 0, 22, 22, 9]),
    "one-token": np.array([4]),
    "batch-by-sequence": np.random.RandomState(1).randint(0, VOCAB, (3, 7)),
}


def embed_layer(ids, dtype, bias):
    policy = DtypePolicy(forward=dtype, backward=dtype)
    return make_layer(
        'name: "e" type: "Embed" bottom: "i" top: "y"\n'
        f'embed_param {{ num_output: {WIDTH} input_dim: {VOCAB}\n'
        f'  bias_term: {str(bias).lower()}\n'
        '  weight_filler { type: "gaussian" std: 1 }\n'
        '  bias_filler { type: "gaussian" std: 1 } }',
        [ids.shape], policy=policy)


def embed_gradients(ids, dtype, bias):
    """(layer's, jax's on plain take, the float32 answer) for one cotangent:
    each a dict of float32 master-weight gradients."""
    layer, params, state = embed_layer(ids, dtype, bias)
    ids = jnp.asarray(ids)
    g = jax.random.normal(jax.random.PRNGKey(3), (*ids.shape, WIDTH), dtype)

    def plain(p, dt=dtype):
        y = jnp.take(p["weight"].astype(dt), ids, axis=0)
        return y + p["bias"].astype(dt) if bias else y

    y, vjp = jax.vjp(lambda p: layer.apply(
        p, state, [ids], train=True, rng=None)[0][0], params)
    want_y, want_vjp = jax.vjp(plain, params)
    assert y.dtype == dtype
    np.testing.assert_array_equal(f32(y), f32(want_y))
    exact = jax.vjp(lambda p: plain(p, F32), params)[1](g.astype(F32))[0]
    return vjp(g)[0], want_vjp(g)[0], exact


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(EMBED_IDS))
def test_embed_vjp_equals_jax_on_plain_take(case, dtype, bias):
    got, want, exact = embed_gradients(EMBED_IDS[case], dtype, bias)
    assert set(got) == set(want) == ({"weight", "bias"} if bias
                                     else {"weight"})
    for name in got:
        assert got[name].dtype == want[name].dtype == jnp.float32
        if dtype == jnp.float32:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                                       atol=1e-6)
            continue
        if name == "bias":      # a plain sum over tokens on both sides
            np.testing.assert_array_equal(f32(got[name]), f32(want[name]))
            continue
        # bf16: jax's scatter-add rounds after every row it adds, this
        # pass sums in float32 and rounds once, so it is the nearer of the
        # two to the float32 answer and within bf16's step of it
        err = np.abs(f32(got[name]) - f32(exact[name]))
        err_jax = np.abs(f32(want[name]) - f32(exact[name]))
        assert err.max() <= err_jax.max() + 1e-6
        assert err.max() <= 2 ** -8 * np.abs(f32(exact[name])).max()
    absent = np.setdiff1d(np.arange(VOCAB), EMBED_IDS[case])
    assert not np.any(f32(got["weight"])[absent])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_embed_past_its_size_limit_keeps_the_gradient(dtype, monkeypatch):
    ids = EMBED_IDS["some-absent"]
    inside = embed_gradients(ids, dtype, False)[0]["weight"]
    # T^2 F = 800 (x 4 MXU passes in float32) is past a limit of 799
    monkeypatch.setattr(dense, "_LOOKUP_PRODUCT_LIMIT",
                        ids.size ** 2 * WIDTH - 1)
    past, want, _ = embed_gradients(ids, dtype, False)
    np.testing.assert_array_equal(f32(past["weight"]), f32(want["weight"]))
    np.testing.assert_allclose(f32(past["weight"]), f32(inside),
                               rtol=2 ** -7, atol=1e-6)


def test_embed_limit_is_on_what_the_product_costs():
    """The compare matrix's product grows with T^2 F, so that is what the
    limit is on; the choice is made from the cotangent's shape at trace
    time (no id is read). The language-model cell's shape is inside."""
    def lowered(count, width, dtype):
        ids = jax.ShapeDtypeStruct((count,), jnp.int32)
        g = jax.ShapeDtypeStruct((count, width), dtype)
        return jax.jit(
            lambda ids, g: dense._lookup_bwd(VOCAB, ids, g)[0]).lower(
                ids, g).as_text()
    for count, width, dtype, product in [
            (8192, 2560, jnp.bfloat16, True),      # the cell
            (8192, 2560, jnp.float32, True),
            (16384, 2560, jnp.bfloat16, True),
            (24576, 2560, jnp.bfloat16, False),
            (16384, 2560, jnp.float32, False),
            (65536, 256, jnp.bfloat16, False)]:
        assert ("dot_general" in lowered(count, width, dtype)) == product, (
            count, width, dtype)


# -- SoftmaxWithLoss ---------------------------------------------------------

def plain_softmax_loss(x, labels, axis, ignore, mode):
    """The layer as it was written before PR 28: float32 log-softmax, the
    label's entry by `take_along_axis`, differentiated by jax."""
    log_p = jnp.moveaxis(jax.nn.log_softmax(x.astype(F32), axis=axis),
                         axis, -1)
    flat = labels.astype(jnp.int32).reshape(log_p.shape[:-1])
    nll = -jnp.take_along_axis(log_p, flat[..., None], axis=-1)[..., 0]
    valid = nll.size
    if ignore is not None:
        mask = flat != ignore
        nll = jnp.where(mask, nll, 0.0)
        valid = jnp.maximum(jnp.sum(mask), 1)
    norm = {"FULL": nll.size, "VALID": valid, "BATCH_SIZE": x.shape[0],
            "NONE": 1}[mode]
    return jnp.sum(nll) / norm, jnp.exp(jnp.moveaxis(log_p, -1, axis))


LOSS_SHAPES = {                       # logits, labels, softmax axis
    "cnn-Nx1000": ((6, 1000), (6,), 1),
    "lm-1xSxV": ((1, 12, 37), (1, 12), 2),
    "spatial-axis1": ((2, 5, 3, 4), (2, 1, 3, 4), 1),
    "lm-axis1": ((3, 9, 4), (3, 4), 1),
}
IGNORE = 2


def loss_layer(case, ignore, mode, dtype, tops='top: "loss"'):
    shape, lshape, axis = LOSS_SHAPES[case]
    loss_param = f"normalization: {mode}"
    if ignore is not None:
        loss_param += f" ignore_label: {ignore}"
    layer, params, state = make_layer(
        f'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" {tops}\n'
        f'softmax_param {{ axis: {axis} }} loss_param {{ {loss_param} }}',
        [shape, lshape], policy=DtypePolicy(forward=dtype, backward=dtype))
    rng = np.random.RandomState(len(case))
    x = jnp.asarray(rng.randn(*shape) * 3, dtype)
    labels = rng.randint(0, shape[axis], lshape)
    labels.reshape(-1)[::3] = IGNORE        # a third of the positions
    return layer, params, state, x, jnp.asarray(labels), axis


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["VALID", "FULL", "BATCH_SIZE", "NONE"])
@pytest.mark.parametrize("ignore", [None, IGNORE], ids=["count-all", "ignore"])
@pytest.mark.parametrize("case", list(LOSS_SHAPES))
def test_softmax_loss_vjp_equals_jax_on_the_plain_formulation(
        case, ignore, mode, dtype):
    layer, params, state, x, labels, axis = loss_layer(
        case, ignore, mode, dtype)
    weight = 1.7        # a loss weight or a loss scale arrives as this

    def system(x):
        return weight * layer.apply(params, state, [x, labels], train=True,
                                    rng=None)[0][0]

    def oracle(x):
        return weight * plain_softmax_loss(x, labels, axis, ignore, mode)[0]
    loss, grad = jax.value_and_grad(system)(x)
    want_loss, want_grad = jax.value_and_grad(oracle)(x)
    assert loss.dtype == jnp.float32 and grad.dtype == dtype
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    # both round a float32 gradient to the bottom's type once
    np.testing.assert_allclose(f32(grad), f32(want_grad), rtol=2e-6,
                               atol=1e-7 if dtype == F32 else 2 ** -9 * float(
                                   jnp.max(jnp.abs(want_grad.astype(F32)))))
    if ignore is not None:
        shape = LOSS_SHAPES[case][0]
        off = jnp.expand_dims(labels.reshape(
            shape[:axis] + shape[axis + 1:]), axis) == ignore
        assert not np.any(f32(grad) * np.asarray(off))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["cnn-Nx1000", "lm-1xSxV"])
def test_softmax_loss_second_top_is_the_probabilities(case, dtype):
    layer, params, state, x, labels, axis = loss_layer(
        case, IGNORE, "VALID", dtype, tops='top: "loss" top: "prob"')
    pick = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def system(x):
        (loss, prob), _ = layer.apply(params, state, [x, labels], train=True,
                                      rng=None)
        return loss + jnp.sum(prob * pick), prob

    def oracle(x):
        loss, prob = plain_softmax_loss(x, labels, axis, IGNORE, "VALID")
        return loss + jnp.sum(prob * pick), prob
    (value, prob), grad = jax.value_and_grad(system, has_aux=True)(x)
    (want, want_prob), want_grad = jax.value_and_grad(oracle,
                                                      has_aux=True)(x)
    assert prob.shape == x.shape and prob.dtype == jnp.float32
    np.testing.assert_allclose(prob, want_prob, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    scale = float(jnp.max(jnp.abs(want_grad.astype(F32))))
    np.testing.assert_allclose(
        f32(grad), f32(want_grad), rtol=1e-5,
        atol=scale * (1e-6 if dtype == F32 else 2 ** -8))


# -- the train step of the tiny SmallThinker recipe --------------------------

@pytest.fixture(scope="module")
def tiny_step_hlo():
    """The tiny recipe's bf16 train step compiled for the cpu platform
    (`Solver.step_hlo_text`): every instruction with its `op_name`."""
    from caffe_mpi_tpu.proto import SolverParameter
    from caffe_mpi_tpu.solver import Solver
    sp = SolverParameter.from_file(
        str(ROOT / "models/smallthinker_21b_a3b/tiny_solver.prototxt"))
    sp.precision = "bf16"
    sp.snapshot, sp.snapshot_after_train = 0, False
    solver = Solver(sp, model_dir=str(ROOT))
    try:
        feeds = {k: jnp.zeros(shape, jnp.int32)
                 for k, (shape, _) in solver.net.feed_specs.items()}
        return (solver.step_hlo_text(feeds),
                solver.net.blob_shapes["logits"])
    finally:
        solver.close()


def instructions(hlo: str, opcode: str):
    """(result shape, op_name) of each `opcode` instruction."""
    return re.findall(
        r"= (\S+) %s\([^\n]*op_name=\"([^\"]*)\"" % re.escape(opcode), hlo)


def elements(shape: str) -> int:
    """Element count of an HLO shape such as `f32[1,32,64]{2,1,0}`."""
    dims = re.match(r"\w+\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def test_tiny_step_has_no_row_scatter_under_embed_or_loss(tiny_step_hlo):
    hlo, _ = tiny_step_hlo
    scatters = instructions(hlo, "scatter")
    assert scatters          # the expert layers' scalar scatters at least
    under = {}
    for shape, op_name in scatters:
        layer = spans.parse_scope(op_name)
        if layer and layer[0] in ("Embed", "SoftmaxWithLoss"):
            under.setdefault(layer[0], []).append(shape)
            # a scalar an index: the result is a vector of the table's rows
            assert re.fullmatch(r"s32\[64\](\{[^}]*\})?", shape), (
                shape, op_name)
            assert "transpose(" in op_name     # filed under the backward
    # the embedding's backward notes one position for each id present,
    # the loss scatters nothing
    assert list(under) == ["Embed"], under
    # and the sum over equal ids is a matrix product under the layer
    assert any(spans.parse_scope(n) == ("Embed", "embed")
               and "transpose(" in n for _, n in instructions(hlo, "dot"))
    assert not any((spans.parse_scope(n) or ("",))[0] == "SoftmaxWithLoss"
                   for _, n in instructions(hlo, "gather"))


def test_tiny_step_has_no_f32_zero_buffer_of_the_logits_shape(tiny_step_hlo):
    """jax's transpose of `take_along_axis` broadcast a float32 zero to the
    logits' element count (a buffer of its own: XLA:CPU's `wrapped_broadcast`
    fusions, XLA:TPU's `broadcast.3260 f32[311164928]`) and scattered the
    labels' cotangents into it."""
    hlo, logits = tiny_step_hlo
    count = int(np.prod(logits))
    assert count == 1 * 32 * 64
    buffers = re.findall(
        r"= (f32\[[\d,]*\])\S* fusion\(([^\n]*)calls=%wrapped_broadcast", hlo)
    assert buffers                      # the pattern still reads this XLA
    assert not [shape for shape, rest in buffers
                if elements(shape) == count and "SoftmaxWithLoss" in rest]
    assert not [s for s, _ in instructions(hlo, "scatter")
                if elements(s) == count]
