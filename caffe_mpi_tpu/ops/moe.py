"""Mixture-of-Experts FFN with expert parallelism (EP).

The reference has no MoE ops (SURVEY §2.7: EP absent); this is part of the
beyond-reference distributed story (DP/TP: parallel/mesh.py, SP:
ops/attention.py, PP: parallel/pipeline.py).

TPU-native design — the GShard dispatch/combine formulation, which is the
shape XLA's GSPMD partitioner understands natively:

  router:   logits = x @ gate -> softmax -> top-k experts per token
  capacity: each expert processes at most C tokens (C from
            capacity_factor); overflow tokens are DROPPED from that
            expert (their combine weight is zero) — the standard GShard
            semantics that keeps every tensor static-shaped for XLA
  dispatch: one-hot (T, E, C) tensor; expert inputs = einsum to (E, C, F)
  experts:  per-expert 2-layer FFN as batched (E, ...) einsums — one MXU
            matmul batched over experts, no Python loop
  combine:  gate-weighted einsum back to (T, F)

`moe_dropless` is the other formulation, for fine-grained experts (many
small ones, several a token) where a (T, E, C) one-hot tensor and dropped
tokens are both unaffordable: the (T, k) choices are flattened and sorted
by expert, the rows gathered, the experts run as grouped matrix products
over the sorted rows (megablox's Pallas kernels; `lax.ragged_dot` on the
CPU), and the results summed back per token under the router's weights.
No capacity, no dropped token, static shapes. An expert is unbiased
matrices: a gated unit of three, (act(x w1) * (x w3)) w2, or, where the
layer holds no `w3`, an ungated one of two, act(x w1) w2. Parameters
(`moe_param`, five recipes use them): the scoring (`route`: top-k then
softmax, sigmoid scores chosen under a selection bias and renormalised, or
the softmax over every expert chosen under the bias and not renormalised,
times a scaling factor), whether the router is the layer's own matrix or
logits handed in, the activation (relu | silu | relu2, relu squared),
whether the experts are gated, and shared experts, one unit of the same
form every token passes through (scope `moe.shared`). It is told which experts it holds (an
expert-parallel share): the router still scores all of them, and the
result is the held experts' part plus, computed here for this chip's own
tokens, the shared experts'. Rows routed to absent experts sort last. They
cost no expert FLOPs, but a buffer of every pair costs everything else on
them, forward and backward (the gather, two selects, the activation, the
weighting, the re-layouts the transposed products need): 75 % and 94 % of
the two recipes' rows. So a layer that holds a share sizes its buffer by a
bound on the rows its held experts receive, 1.5 x their average share,
and the device, which has the live count before the first gather, takes
the buffer of every pair instead whenever the count reaches the bound
(`_bounded_rows`, scope `moe.fallback`): a skewed router costs time, never
a row.

Expert parallelism = shard the E dimension (expert weights AND the
(E, C, ...) activation tensors) over a mesh axis via sharding
constraints; GSPMD then partitions the batched einsums per-expert and
inserts the token all-to-alls that a hand-written EP backend (DeepSpeed /
Tutel style) performs explicitly. No shard_map needed — this op composes
with DP/TP sharding on the same mesh.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils import spans
from ..utils.spans import (MOE_COMBINE as COMBINE, MOE_DISPATCH as DISPATCH,
                           MOE_EXPERTS as EXPERTS, MOE_FALLBACK as FALLBACK,
                           MOE_ROUTE as ROUTE, MOE_SHARED as SHARED)

ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
               "relu2": lambda t: jnp.square(jax.nn.relu(t))}


def init_moe_params(key, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32) -> dict:
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate": jax.random.normal(kg, (d_model, n_experts), dtype) * 0.02,
        "w1": jax.random.normal(k1, (n_experts, d_model, d_hidden),
                                dtype) * s1,
        "b1": jnp.zeros((n_experts, d_hidden), dtype),
        "w2": jax.random.normal(k2, (n_experts, d_hidden, d_model),
                                dtype) * s2,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def shard_experts(params: dict, mesh, expert_axis: str = "model") -> dict:
    """Place expert-major weights with dim 0 (E) sharded over the mesh
    axis — each device holds n_experts / axis_size experts."""
    def put(name, x):
        if name == "gate":
            return jax.device_put(x, NamedSharding(mesh, P()))
        spec = [expert_axis] + [None] * (x.ndim - 1)
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return {k: put(k, v) for k, v in params.items()}


def moe_ffn(params: dict, x: jnp.ndarray, *, top_k: int = 1,
            capacity_factor: float = 2.0, mesh=None,
            expert_axis: str = "model"):
    """x: (T, F) tokens -> (T, F), plus aux load-balancing loss.

    Returns (y, aux) where aux is the Switch/GShard auxiliary loss
    n_experts * sum_e(frac_tokens_e * mean_prob_e) — add it (scaled by a
    small coefficient) to the training loss to keep routing balanced. With `mesh`, the expert dim of weights and dispatched
    activations is constraint-sharded over `expert_axis` (EP)."""
    t, f = x.shape
    e = params["w1"].shape[0]
    cap = max(int(capacity_factor * top_k * t / e), top_k)
    cap = min(cap, t)

    logits = x @ params["gate"]                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k routing with per-expert position assignment
    combine = jnp.zeros((t, e, cap), x.dtype)
    dispatch_m = jnp.zeros((t, e, cap), bool)
    mask_so_far = jnp.zeros((t, e), bool)
    counts = jnp.zeros((e,), jnp.int32)
    for _ in range(top_k):
        masked = jnp.where(mask_so_far, -jnp.inf, logits)
        choice = jnp.argmax(masked, axis=-1)          # (T,)
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)
        pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - onehot  # (T,E)
        keep = (onehot > 0) & (pos < cap)
        gate_w = jnp.take_along_axis(probs, choice[:, None], axis=1)[:, 0]
        slot = keep[:, :, None] * jax.nn.one_hot(pos, cap, dtype=x.dtype)
        combine = combine + slot * gate_w[:, None, None]
        # Dispatch comes from the routing decision itself, not from
        # thresholding combine: a routed token whose gate weight
        # underflows to 0 in low precision must still reach its expert.
        dispatch_m = dispatch_m | (slot > 0)
        counts = counts + jnp.sum(onehot * keep, axis=0)
        mask_so_far = mask_so_far | (onehot > 0)

    dispatch = dispatch_m.astype(x.dtype)             # (T, E, C)

    def ep(v, spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(*spec)))
        return v

    # dispatch tokens to experts: (E, C, F), sharded over experts
    xe = ep(jnp.einsum("tec,tf->ecf", dispatch, x),
            (expert_axis, None, None))
    h = jax.nn.relu(jnp.einsum("ecf,efh->ech", xe, params["w1"])
                    + params["b1"][:, None, :])
    h = ep(h, (expert_axis, None, None))
    ye = jnp.einsum("ech,ehf->ecf", h, params["w2"]) \
        + params["b2"][:, None, :]
    ye = ep(ye, (expert_axis, None, None))
    y = jnp.einsum("tec,ecf->tf", combine, ye)        # back to tokens

    # GShard aux loss: encourages uniform routing
    frac_tokens = jnp.mean((dispatch.sum(2) > 0).astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * e
    return y, aux


def moe_ffn_dense_reference(params: dict, x: jnp.ndarray, *,
                            top_k: int = 1) -> jnp.ndarray:
    """Unbatched per-expert loop, no capacity limit — the numerical oracle
    for tests (matches moe_ffn when no tokens overflow)."""
    logits = x @ params["gate"]
    probs = jax.nn.softmax(logits, axis=-1)
    e = params["w1"].shape[0]
    _, topi = jax.lax.top_k(logits, top_k)
    y = jnp.zeros_like(x)
    for k in range(top_k):
        idx = topi[:, k]
        gate_w = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
        for ei in range(e):
            sel = idx == ei
            h = jax.nn.relu(x @ params["w1"][ei] + params["b1"][ei])
            out = h @ params["w2"][ei] + params["b2"][ei]
            y = y + jnp.where(sel[:, None], out * gate_w[:, None], 0.0)
    return y


# -- dropless, sorted-by-expert formulation ---------------------------------

ROW_TILE = 512   # rows of a grouped product's tile, the row bound's unit


def _gmm_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(rows, contraction, output) tile of a grouped product: 512 rows,
    and of each weight dimension the largest multiple of 128 up to 1280
    that divides it, so that a tile never straddles the matrix."""
    side = lambda d: next((t for t in (1280, 1024, 768, 512, 384, 256, 128)
                           if d % t == 0), d)
    return (ROW_TILE if m % ROW_TILE == 0 else 128 if m % 128 == 0 else m,
            side(k), side(n))


@jax.custom_vjp
def _gmm(rows, bank, sizes):
    """rows (M, K) x bank (G, K, N), rows sorted by group, `sizes` (G,)
    rows a group -> (M, N), by the Pallas grouped matrix product
    (jax.experimental.pallas.ops.tpu.megablox). It visits only the row
    tiles that hold a group's rows; what lies past the last group is
    left unwritten, in all three products. A custom VJP of its own so
    that each of the three products (the forward, the rows' gradient, the
    bank's gradient) gets tiles sized for its own shapes."""
    return _gmm_fwd(rows, bank, sizes)[0]


def _megablox():
    # the package re-exports its `gmm` FUNCTION over the module of the
    # same name; the two raw kernels live in the module
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_fwd(rows, bank, sizes):
    backend = _megablox()
    m, k = rows.shape
    with spans.phase(spans.KERNEL, kernel="gmm", branch="default"):
        out = backend.gmm(rows, bank, sizes, rows.dtype,
                          _gmm_tiles(m, k, bank.shape[2]))
    return out, (rows, bank, sizes)


def _gmm_bwd(res, grad):
    backend = _megablox()
    rows, bank, sizes = res
    m, k = rows.shape
    n = bank.shape[2]
    with spans.phase(spans.KERNEL, kernel="gmm", branch="default"):
        d_rows = backend.gmm(grad, bank, sizes, rows.dtype,
                             _gmm_tiles(m, n, k), transpose_rhs=True)
    with spans.phase(spans.KERNEL, kernel="tgmm", branch="default"):
        d_bank = backend.tgmm(rows.swapaxes(0, 1), grad, sizes, bank.dtype,
                              _gmm_tiles(m, k, n))
    return d_rows, d_bank, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_dot(rows, bank, sizes):
    """Grouped matrix product over rows sorted by group: `lax.ragged_dot`
    on the cpu platform (the Pallas kernel is TPU-only), `_gmm` on any
    other. XLA:TPU's own `ragged_dot` was compared on the chip and is the
    slower of the two (25.4 against 22.1 ms for the cell's expert layer,
    forward and backward; PERF.md section 6, PR 27); it also drops the
    `moe.experts` scope from its kernels' `op_name`."""
    return jax.lax.platform_dependent(rows, bank, sizes,
                                      cpu=jax.lax.ragged_dot, default=_gmm)


def _ragged_dot_t(rows, bank, sizes, grad):
    return jax.vjp(lambda r, b: jax.lax.ragged_dot(r, b, sizes), rows,
                   bank)[1](grad)


def grouped_dot_t(rows, bank, sizes, grad):
    """`grouped_dot(rows, bank, sizes)`'s two transposes on `grad`: the
    rows' gradient (M, K) and the bank's (G, K, N)."""
    return jax.lax.platform_dependent(
        rows, bank, sizes, grad, cpu=_ragged_dot_t,
        default=lambda *operands: _gmm_bwd(operands[:3], operands[3])[:2])


# The sorted buffer's row p is pair order[p], pair q sits at row inv[q], pair
# q = j * T + t is token t's j-th choice (choice-major, so that the pairs'
# (k, T, F) view splits the leading axis: token-major, the (T, k, F) view
# cost a 0.9 ms re-layout each way). The buffer holds the first R rows of
# that order (`order` arrives cut to R; R = k * T holds every pair). Moving
# rows between the two orders is a permutation, whose transpose is the
# inverse permutation: a gather both ways. A buffer of R < k * T rows holds
# fewer than R live ones, so its last row is dead and zero, and a pair whose
# row lies past R reads that one: the gather clips its indices, where
# `jnp.take`'s own out-of-range rule (fill) costs a select over all k * T
# rows after it (0.8 ms; PERF.md section 6, PR 32). Left to `jnp.take`'s
# own transpose rule the backward pass is a scatter-add of T * k rows, which
# XLA:TPU runs an order of magnitude below a gather's speed (PERF.md
# section 6, PR 27).

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """(T, F) tokens -> (R, F) rows in sorted order."""
    return jnp.take(x, order % x.shape[0], axis=0, mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, order, inv, k):
    """(R, F) rows in sorted order, dead ones zero -> (T, F): each token's
    k rows summed (in float32). The transpose of `_dispatch`."""
    back = _unsort(rows, inv).reshape(k, -1, rows.shape[1])
    return jnp.sum(back.astype(jnp.float32), axis=0).astype(rows.dtype)


def _unsort(rows, inv):
    """rows[inv] over every pair: the last row for a pair past the end."""
    return jnp.take(rows, inv, axis=0, mode="clip")


_dispatch.defvjp(
    lambda x, order, inv, k: (_dispatch(x, order, inv, k), (order, inv)),
    lambda k, res, g: (_combine(g, *res, k), None, None))
_combine.defvjp(
    lambda rows, order, inv, k: (_combine(rows, order, inv, k),
                                 (order, inv)),
    lambda k, res, g: (_dispatch(g, *res, k), None, None))


@jax.custom_vjp
def _permute(values, order, inv):
    """values[order], (k * T,) -> (R,), under the same pair of orders."""
    return jnp.take(values, order, axis=0, mode="clip")


_permute.defvjp(
    lambda values, order, inv: (_permute(values, order, inv), inv),
    lambda inv, g: (_unsort(g, inv), None, None))


def route(logits: jnp.ndarray, top_k: int, scoring: str = "softmax",
          select_bias: jnp.ndarray | None = None, scale: float = 1.0):
    """(T, E) router logits -> (weights (T, k) float32, expert ids (T, k)).
    "softmax": the k largest logits, softmax over those in float32 (which
    equals softmax over all, select, renormalise). "sigmoid": s =
    sigmoid(logits) in float32, the k largest of s + select_bias (E,), and
    weights scale * s / (sum of the chosen s + 1e-20): the bias selects and
    does not weigh. "softmax_all": p = softmax(logits) over every expert in
    float32, the k largest of p + select_bias, and weights scale * p of the
    chosen, not renormalised: at k = 1 the chosen expert's own
    probability."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        top, ids = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top, axis=-1), ids
    if scoring not in ("sigmoid", "softmax_all"):
        raise ValueError(f"moe scoring {scoring!r}: softmax, sigmoid or "
                         f"softmax_all")
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = scores if select_bias is None \
        else scores + select_bias.astype(jnp.float32)
    _, ids = jax.lax.top_k(biased, top_k)
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if scoring == "softmax_all":
        return scale * top, ids
    return scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20), ids


def _row_bound(pairs: int, held: int, experts: int,
               shares: float = 1.5) -> int:
    """Rows of the sorted buffer for `pairs` (token, choice) pairs of which
    the `held` of `experts` experts receive their share on average: the
    smallest multiple of the row tile at or over `shares` x that share, and
    `pairs` where that is no fewer. 1.5 by default (the held experts of
    the first two recipes received 0.79-1.17 x theirs over every seed read
    on the chip, PERF.md section 6, PR 32); `moe_param.row_bound` says
    otherwise where a fresh router's skew is wider (8 held of 128 behind
    ungated relu^2 experts: 0.50-1.78 x over 116 layers read, PR 40)."""
    tiles = math.ceil(shares * pairs * held / (experts * ROW_TILE))
    return min(tiles * ROW_TILE, pairs)


def _unit(act, a, b=None):
    """What lies between an expert's products: act(x w1), times x w3 where
    the expert is gated."""
    return act(a) if b is None else act(a) * b


def _sorted_rows(dot, k, act, x, w, banks, order, inv, sizes):
    """The held experts over the sorted buffer's first R = len(order) rows,
    which have to hold every live one: x (T, F), w (k * T,) the pairs'
    weights, banks (w1, w3, w2) of gated experts or (w1, w2) of ungated
    ones, `dot` the grouped product -> (y (T, F), what the backward pass
    reads again: xs (R, F), the products from xs (R, H) (two, or one), ys
    (R, F))."""
    *ups, w2 = banks
    live = _live(order, sizes)
    with jax.named_scope(DISPATCH):
        xs = jnp.where(live, _dispatch(x, order, inv, k), 0)
    with jax.named_scope(EXPERTS):
        pre = tuple(dot(xs, up, sizes) for up in ups)
        ys = dot(_unit(act, *pre), w2, sizes)
    with jax.named_scope(COMBINE):
        y = _combine(_weigh(ys, _permute(w, order, inv), live),
                     order, inv, k)
    return y, (xs, pre, ys)


def _live(order, sizes):
    """(R, 1) mask of the buffer's live rows, which sort first. Rows past
    the last group are never written by the grouped products, forward or
    backward: selects on this mask, not products (0 * NaN), keep what is
    left there out of the result and out of every gradient."""
    return (jnp.arange(order.shape[0]) < jnp.sum(sizes))[:, None]


def _weigh(ys, w, live):
    return jnp.where(live, ys, 0) * w[:, None].astype(ys.dtype)


def _sorted_rows_bwd(k, act, w, banks, order, inv, sizes, saved, g):
    """Gradients of `_sorted_rows`' y with respect to (x, w, banks) from
    what it saved: each stage's own transpose, in the stages' scopes."""
    *ups, w2 = banks
    xs, pre, ys = saved
    live = _live(order, sizes)
    with jax.named_scope(COMBINE):
        d_ys, d_w = jax.vjp(lambda ys, w: _weigh(ys, w, live), ys,
                            _permute(w, order, inv)
                            )[1](_dispatch(g, order, inv, k))
        d_w = _unsort(d_w, inv)
    with jax.named_scope(EXPERTS):
        h, unit_bwd = jax.vjp(functools.partial(_unit, act), *pre)
        d_h, d_w2 = grouped_dot_t(h, w2, sizes, d_ys)
        d_xs, d_ups = zip(*(grouped_dot_t(xs, up, sizes, d)
                            for up, d in zip(ups, unit_bwd(d_h))))
    with jax.named_scope(DISPATCH):
        d_x = _combine(jnp.where(live, functools.reduce(operator.add, d_xs),
                                 0), order, inv, k)
    return d_x, d_w, (*d_ups, d_w2)


# Fewer rows than pairs: the bound holds every live row, and a dead one
# after them, unless the router sends the held experts 1.5 x their share
# or more, which the device decides from the live count before the first
# gather. `lax.cond` runs one branch: the bounded one, or (scope
# `moe.fallback`) the buffer of every pair, so that no row is ever
# dropped. Differentiated by jax, a `cond` returns both branches'
# residuals, the untaken one's as zeros at full size; this rule keeps the
# bounded branch's alone, and the fallback's backward pass runs its forward
# pass again.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bounded_rows(k, act, bound, x, w, banks, order, inv, sizes):
    return _bounded_rows_fwd(k, act, bound, x, w, banks, order, inv,
                             sizes)[0]


def _fallback(k, act, x, w, banks, order, inv, sizes):
    """The buffer of every pair. Its grouped products are XLA's own
    `ragged_dot` on every platform: 15 % slower on the chip than the Pallas
    kernels (`grouped_dot`), but eleven more of those a layer, at a second
    row count, cost every start of the program 8 s of tracing and of
    loading a step two thirds larger (PERF.md section 6, PR 32), for a
    branch that a balanced router never takes."""
    with jax.named_scope(FALLBACK):
        return _sorted_rows(jax.lax.ragged_dot, k, act, x, w, banks, order,
                            inv, sizes)[0]


def _bounded_rows_fwd(k, act, bound, x, w, banks, order, inv, sizes):
    bounded = lambda: _sorted_rows(grouped_dot, k, act, x, w, banks,
                                   order[:bound], inv, sizes)
    wide, narrow = (bound, x.shape[1]), (bound, banks[0].shape[2])
    zeros = lambda shape: jnp.zeros(shape, x.dtype)
    y, saved = jax.lax.cond(
        jnp.sum(sizes) < bound, bounded,
        lambda: (_fallback(k, act, x, w, banks, order, inv, sizes),
                 (zeros(wide), tuple(zeros(narrow) for _ in banks[:-1]),
                  zeros(wide))))
    return y, (x, w, banks, order, inv, sizes, saved)


def _bounded_rows_bwd(k, act, bound, res, g):
    x, w, banks, order, inv, sizes, saved = res
    return jax.lax.cond(
        jnp.sum(sizes) < bound,
        lambda: _sorted_rows_bwd(k, act, w, banks, order[:bound], inv, sizes,
                                 saved, g),
        lambda: jax.vjp(lambda x, w, banks: _fallback(
            k, act, x, w, banks, order, inv, sizes), x, w, banks)[1](g)
    ) + (None, None, None)


_bounded_rows.defvjp(_bounded_rows_fwd, _bounded_rows_bwd)


def moe_dropless(params: dict, x: jnp.ndarray, router_in: jnp.ndarray, *,
                 top_k: int, first_expert: int = 0,
                 scoring: str = "softmax", scale: float = 1.0,
                 activation: str = "relu", row_bound: float = 1.5):
    """x, router_in: (T, F) -> (y (T, F), rows (E_held,) float32).

    params: `gate` (F, E) scores every expert (`route`; `select_bias` (E,)
    with sigmoid and softmax_all scoring), and where there is none
    `router_in` (T, E) is the logits themselves; the banks `w1`, `w3`
    (E_held, F, H) and `w2` (E_held, H, F) are experts first_expert ..
    first_expert + E_held - 1, gated units without biases: (act(x w1) * (x
    w3)) w2, act = relu | silu | relu2; without `w3` the experts are
    ungated, act(x w1) w2. y is the sum over a token's chosen experts
    THAT ARE HELD of weight * expert(x): what absent experts would add is
    left out and the weights are not renormalised over the held ones.
    With `shared_w1`, `shared_w3` (F, Hs) and `shared_w2` (Hs, F), the
    shared experts' (act(x shared_w1) * (x shared_w3)) shared_w2 is added
    for every token, unweighted (act(x shared_w1) shared_w2 without
    `shared_w3`).
    rows[e] counts the (token, choice) pairs held expert e received.

    The (token, choice) pairs are sorted by expert, those of absent
    experts last, and the sorted buffer holds the first R rows of that
    order: R = `_row_bound`, static, top_k * T when every expert is held.
    With fewer, R is `row_bound` x the held experts' average share, and the device
    takes the buffer of all top_k * T rows instead (`_bounded_rows`)
    whenever the live rows reach it: nothing is dropped either way."""
    held = params["w1"].shape[0]
    act = ACTIVATIONS[activation]
    with jax.named_scope(ROUTE):
        logits = jnp.dot(router_in, params["gate"],
                         preferred_element_type=jnp.float32) \
            if "gate" in params else router_in
        weights, ids = route(logits, top_k, scoring,
                             params.get("select_bias"), scale)
    with jax.named_scope(DISPATCH):
        local = ids.T.reshape(-1) - first_expert
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    bound = _row_bound(order.shape[0], held, logits.shape[1], row_bound)
    banks = tuple(params[name] for name in ("w1", "w3", "w2")
                  if name in params)
    routed = (x, weights.T.reshape(-1), banks, order, inv, sizes)
    if bound < order.shape[0]:
        y = _bounded_rows(top_k, act, bound, *routed)
    else:
        y = _sorted_rows(grouped_dot, top_k, act, *routed)[0]
    if "shared_w1" in params:
        with jax.named_scope(SHARED):
            h = act(x @ params["shared_w1"])
            if "shared_w3" in params:
                h = h * (x @ params["shared_w3"])
            y = y + h @ params["shared_w2"]
    return y, sizes.astype(jnp.float32)
