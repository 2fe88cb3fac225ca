"""Plain float32 reference for the benchmark's language-model configuration:
SmallThinker-21BA3B-Instruct (https://huggingface.co/PowerInfer/
SmallThinker-21BA3B-Instruct/blob/main/config.json; family described in
arXiv:2507.20984), forward pass, loss and, through `jax.grad`, gradients.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: plain `jax.numpy`, float32, every matrix product
under `jax.default_matmul_precision("highest")`, no kernels, attention by an
explicit mask, the experts by a loop over the experts held. It knows the
program only through `from_net`, which reads the program's freshly
initialised arrays out of Caffe's blob layouts (`[out, in]` for a product).

The layer equations. Hidden D; a layer's input h (N, S, D):

1. router logits from the layer's INPUT, before any norm: r = h W_r,
   W_r (D, E); under jax.grad r is a constant (the recipe trains neither
   the router nor anything through it);
2. a = rms(h) * g1, rms(x) = x / sqrt(mean(x^2, -1) + eps), in float32;
3. q = a W_q -> (N, S, H, d); k = a W_k, v = a W_v -> (N, S, Hkv, d); no
   biases. Where `rope_layout[l]` is 1, q and k are rotated over all d
   dimensions, rotate-half convention, positions 0..S-1 in each sequence,
   no scaling;
4. query head n attends key/value head n // (H / Hkv), scale 1/sqrt(d);
   key j is visible to query i iff j <= i and, where
   `sliding_window_layout[l]` is 1, j > i - window. Softmax in float32.
   u = h + concat(o) W_o;
5. m = rms(u) * g2;
6. per token: I = the top_k largest of r, w = softmax(r[I]) over those;
7. expert e: f_e(m) = (relu(m G_e) * (m U_e)) D_e, no biases;
8. out = u + sum over the e in I THAT THIS CHIP HOLDS of w_e f_e(m). What
   the absent experts would add is left out; w is not renormalised.

After the last layer: rms * g, logits = x W_head over this chip's slice of
the vocabulary, mean token cross-entropy from a float32 log-softmax.
Embedding and head are untied; no auxiliary loss.

Departures from the published model are the configuration file's `assumed`
list (benchmarks/configs/smallthinker_21b_a3b.json): the router reads h,
not rms(h); no biases and no q/k norm; one chip's share of experts and
vocabulary.

For the chip, `hidden` computes attention in blocks of queries and
`logits_block` the head in blocks of the vocabulary, so that S = 8192 fits
beside the optimizer state; `loss_blocked` is `loss` in such blocks, with
layers, query blocks, experts and vocabulary blocks computed again in the
backward pass, so that `jax.grad` of it fits there too. `operand_dtype`
rounds both operands of every matrix product to a narrower type first: the
reading "one precision below the configuration's" that a tolerance has to
fail (PERF.md section 2).

The counting functions at the end (`macs_per_sample`, `param_count`,
`flash_cost`, `grouped_cost`) are the benchmark's own count of what the
algorithm needs, from shapes alone; mfu and the roofline shares read them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

TILE = 128   # the MXU's width: masks are counted in tiles of this size


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    window_layout: tuple    # per layer: 1 = sliding window, 0 = global
    rope_layout: tuple      # per layer: 1 = rotary positions, 0 = none
    experts: int            # the router's width: every expert of the model
    experts_held: int       # of which this chip holds these,
    first_expert: int       # starting here
    top_k: int
    expert_width: int
    eps: float


def sizes_from_config(config: dict, preset: dict | None = None) -> Sizes:
    """From a configuration file's keys (the published config.json's own
    names); a rehearsal preset's `sizes` overrides them."""
    c = {**config, **(preset or {}).get("sizes", {})}
    layers = c["num_hidden_layers"]
    return Sizes(
        vocab=c["vocab_size"], hidden=c["hidden_size"], layers=layers,
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], window=c["sliding_window_size"],
        rope_theta=float(c["rope_theta"]),
        window_layout=tuple(c["sliding_window_layout"][:layers]),
        rope_layout=tuple(c["rope_layout"][:layers]),
        experts=c["published"]["moe_num_primary_experts"],
        experts_held=c["moe_num_primary_experts"],
        first_expert=c["first_expert"],
        top_k=c["moe_num_active_primary_experts"],
        expert_width=c["moe_ffn_hidden_size"], eps=c["rms_norm_eps"])


def sizes_record(sz: Sizes) -> dict:
    """`sz` as a run record carries it (JSON: tuples become lists)."""
    return dataclasses.asdict(sz)


def sizes_from_record(record: dict) -> Sizes:
    return Sizes(**{k: tuple(v) if isinstance(v, list) else v
                    for k, v in record.items()})


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def _mm(a, b, operand_dtype=None):
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(jnp.float32)
        b = b.astype(operand_dtype).astype(jnp.float32)
    return jnp.matmul(a, b)


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate(x, theta):
    """(N, S, heads, d) -> the same with rotary positions 0..S-1,
    rotate-half: x cos + [-x2, x1] sin, angle_i = pos / theta^(2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def visible(rows, cols, window: int):
    """The mask of equation 4 for query positions `rows` against key
    positions `cols`; window 0 = causal only."""
    ok = cols[None, :] <= rows[:, None]
    if window:
        ok &= cols[None, :] > rows[:, None] - window
    return ok


def attention(lp, a, sz: Sizes, l: int, q_block, dt):
    n, s, _ = a.shape
    group = sz.heads // sz.kv_heads
    q = _mm(a, lp["wq"], dt).reshape(n, s, sz.heads, sz.head_dim)
    k = _mm(a, lp["wk"], dt).reshape(n, s, sz.kv_heads, sz.head_dim)
    v = _mm(a, lp["wv"], dt).reshape(n, s, sz.kv_heads, sz.head_dim)
    if sz.rope_layout[l]:
        q, k = rotate(q, sz.rope_theta), rotate(k, sz.rope_theta)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    window = sz.window if sz.window_layout[l] else 0
    cols = jnp.arange(s)
    if dt is not None:
        q, k, v = (x.astype(dt).astype(jnp.float32) for x in (q, k, v))

    def block(start):
        rows = start + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        scores = jnp.einsum("nqhd,nkhd->nhqk", qb, k) / math.sqrt(sz.head_dim)
        scores = jnp.where(visible(rows, cols, window)[None, None], scores,
                           -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if dt is not None:
            p = p.astype(dt).astype(jnp.float32)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    q_block = min(q_block or s, s)
    if s % q_block:
        raise ValueError(f"query block {q_block} does not divide {s}")
    # under jax.grad a block's scores are computed again, not kept
    o = jax.lax.map(jax.checkpoint(block),
                    jnp.arange(0, s, q_block))          # (blocks, n, qb, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(n, s, sz.heads * sz.head_dim)
    return _mm(o, lp["wo"], dt)


def route(r, top_k: int):
    """Equation 6: (indices (.., k), weights (.., k))."""
    top, idx = jax.lax.top_k(r, top_k)
    return idx, jax.nn.softmax(top, axis=-1)


def experts(lp, m, r, sz: Sizes, dt, first_expert=None, held=None):
    """Equations 6-8 without the residual: the part of the expert layer's
    result that experts first_expert .. first_expert + held - 1 give.
    `lp["gate"|"up"|"down"]` hold those experts' matrices."""
    first = sz.first_expert if first_expert is None else first_expert
    held = sz.experts_held if held is None else held
    idx, w = route(r, sz.top_k)

    @jax.checkpoint   # under jax.grad: one expert's intermediates at a time
    def add(y, expert):
        e, gate, up, down = expert
        chosen = idx == first + e                          # (.., k)
        w_e = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1)  # 0 if not chosen
        f = _mm(jax.nn.relu(_mm(m, gate, dt)) * _mm(m, up, dt), down, dt)
        return y + jnp.where(jnp.any(chosen, -1)[..., None],
                             w_e[..., None] * f, 0.0), None
    # a loop over the held experts, one after the other, each over every
    # token and masked to the tokens that chose it
    banks = (jnp.arange(held), lp["gate"][:held], lp["up"][:held],
             lp["down"][:held])
    return jax.lax.scan(add, jnp.zeros_like(m), banks)[0]


def layer(lp, h, sz: Sizes, l: int, q_block=None, dt=None):
    # routing is a constant of the training step, as in the recipe (the
    # configuration's `assumed`): neither the router nor anything through
    # it has a gradient
    r = jax.lax.stop_gradient(_mm(h, lp["router"], dt))
    u = h + attention(lp, rms(h, lp["g1"], sz.eps), sz, l, q_block, dt)
    return u + experts(lp, rms(u, lp["g2"], sz.eps), r, sz, dt)


@_highest
def hidden(params, tokens, sz: Sizes, q_block=None, operand_dtype=None,
           remat=False):
    """(N, S) token ids -> (N, S, D), the last norm applied. `remat`: under
    jax.grad keep only each layer's input and compute the layer again in
    the backward pass (the timed size on the chip)."""
    h = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    for l, lp in enumerate(params["layers"]):
        step = functools.partial(layer, sz=sz, l=l, q_block=q_block,
                                 dt=operand_dtype)
        h = (jax.checkpoint(step) if remat else step)(lp, h)
    return rms(h, params["g_f"], sz.eps)


@_highest
def logits_block(params, x, lo: int, hi: int, operand_dtype=None):
    """Logits of vocabulary rows lo..hi-1 of this chip's slice."""
    return _mm(x, params["head"][:, lo:hi], operand_dtype)


def forward(params, tokens, sz: Sizes, q_block=None, operand_dtype=None):
    x = hidden(params, tokens, sz, q_block, operand_dtype)
    return logits_block(params, x, 0, sz.vocab, operand_dtype)


def loss(params, tokens, labels, sz: Sizes, q_block=None):
    """Mean token cross-entropy over the slice, float32 log-softmax."""
    logp = jax.nn.log_softmax(forward(params, tokens, sz, q_block), axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


loss_and_grads = jax.value_and_grad(loss)


@_highest
def loss_blocked(params, tokens, labels, sz: Sizes, q_block, vocab_block,
                 operand_dtype=None, positions=None):
    """`loss` for the chip at the timed size, where the (tokens, vocabulary)
    logits may not be held whole, least of all under jax.grad: the
    log-sum-exp from blocks of the vocabulary, each computed again in the
    backward pass, and the label's logit as a row-wise product. The same
    number as `loss`. `positions` (a planted fault for the controls):
    the mean over the first so many positions of each sequence only."""
    x = hidden(params, tokens, sz, q_block, operand_dtype, remat=True)
    if positions is not None:
        x, labels = x[:, :positions], labels[:, :positions]
    x = x.reshape(-1, x.shape[-1])
    labels = labels.astype(jnp.int32).reshape(-1)
    head = params["head"]                                  # (D, V)
    block = min(vocab_block, sz.vocab)
    n_blocks = -(-sz.vocab // block)
    padded = jnp.pad(head, ((0, 0), (0, n_blocks * block - sz.vocab)))

    @jax.checkpoint
    def lse_block(i):
        logits = _mm(x, jax.lax.dynamic_slice_in_dim(
            padded, i * block, block, axis=1), operand_dtype)
        live = i * block + jnp.arange(block) < sz.vocab
        return jax.nn.logsumexp(jnp.where(live[None, :], logits, -jnp.inf),
                                axis=-1)
    lse = jax.nn.logsumexp(jax.lax.map(lse_block, jnp.arange(n_blocks)),
                           axis=0)
    w = jnp.take(head, labels, axis=1).T                   # (tokens, D)
    if operand_dtype is not None:
        x, w = (a.astype(operand_dtype).astype(jnp.float32) for a in (x, w))
    return jnp.mean(lse - jnp.sum(x * w, axis=-1))


def from_net(net_params: dict, sz: Sizes) -> dict:
    """The reference's weights out of the program's blobs (the prototxt
    `models/generate_models.py smallthinker` emits): `Embed.weight` (V, D);
    `Attention.qkv_weight` ((H + 2 Hkv) d, D), rows q then k then v, and
    `proj_weight` (D, H d), both [out, in]; `RMSNorm.scale`; `MoE.gate` (D,
    E), `w1` = G, `w3` = U (held, D, W), `w2` = D (held, W, D);
    `InnerProduct.weight` (V, D). A linear map, so it carries gradients
    the same way."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    layers = []
    for l in range(sz.layers):
        qkv = f32(net_params[f"blk{l}/attn"]["qkv_weight"])
        moe = net_params[f"blk{l}/moe"]
        layers.append({
            "g1": f32(net_params[f"blk{l}/ln1"]["scale"]),
            "wq": qkv[:nq].T, "wk": qkv[nq:nq + nkv].T,
            "wv": qkv[nq + nkv:].T,
            "wo": f32(net_params[f"blk{l}/attn"]["proj_weight"]).T,
            "g2": f32(net_params[f"blk{l}/ln2"]["scale"]),
            "router": f32(moe["gate"]), "gate": f32(moe["w1"]),
            "up": f32(moe["w3"]), "down": f32(moe["w2"])})
    return {"embed": f32(net_params["embed"]["weight"]), "layers": layers,
            "g_f": f32(net_params["ln_f"]["scale"]),
            "head": f32(net_params["logits"]["weight"]).T}


# -- counts, from shapes alone -----------------------------------------------

def param_count(sz: Sizes) -> int:
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    attention = sz.hidden * (nq + 2 * nkv) + nq * sz.hidden
    expert = 3 * sz.hidden * sz.expert_width
    per_layer = (attention + sz.hidden * sz.experts
                 + sz.experts_held * expert + 2 * sz.hidden)
    return (sz.layers * per_layer + 2 * sz.vocab * sz.hidden + sz.hidden)


def visible_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head the mask of equation 4 leaves."""
    w = min(window or seq, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def visible_tiles(seq: int, window: int, tile: int = TILE) -> int:
    """Tiles of `tile` x `tile` (query, key) pairs that hold at least one
    visible pair: what a tiled kernel cannot avoid visiting."""
    n = -(-seq // tile)
    count = 0
    for qi in range(n):
        first_row, last_row = qi * tile, min((qi + 1) * tile, seq) - 1
        lo = max(first_row - window + 1, 0) // tile if window else 0
        count += last_row // tile - lo + 1
    return count


def macs_per_sample(sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates of one sequence of `seq` tokens:
    projections, scores and values over the visible pairs only, the
    router, the held experts at their expected top_k * held / experts
    rows a token, and the head. The embedding is a gather."""
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    projections = seq * (sz.hidden * (nq + 2 * nkv) + nq * sz.hidden)
    router = seq * sz.hidden * sz.experts
    held = seq * sz.top_k * sz.experts_held * 3 * sz.hidden \
        * sz.expert_width // sz.experts
    total = seq * sz.hidden * sz.vocab
    for l in range(sz.layers):
        window = sz.window if sz.window_layout[l] else 0
        total += (projections + router + held
                  + 2 * visible_pairs(seq, window) * nq)
    return total


# FLOPs a (query, key) pair of one head costs each flash kernel, in units
# of the head size d: forward QK^T and PV; dQ recomputes QK^T, then dO V^T
# and dS K; dK/dV recomputes QK^T, then P^T dO, dO V^T and dS^T Q
FLASH_FLOPS_PER_PAIR = {"flash_fwd": 4, "flash_dq": 6, "flash_dkv": 8}


def flash_cost(kernel: str, sz: Sizes, l: int, batch: int, seq: int,
               itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one call of a flash kernel needs in layer `l`: the
    matrix products over the visible 128 x 128 tiles, and each operand
    read and each result written once (q, o, dO and dQ over the query
    heads, k, v, dK and dV over the key/value heads, the float32 row
    statistics)."""
    window = sz.window if sz.window_layout[l] else 0
    pairs = visible_tiles(seq, window) * TILE * TILE
    flops = (FLASH_FLOPS_PER_PAIR[kernel] * sz.head_dim * pairs
             * sz.heads * batch)
    q = batch * seq * sz.heads * sz.head_dim * itemsize
    kv = batch * seq * sz.kv_heads * sz.head_dim * itemsize
    stats = batch * seq * sz.heads * 4
    nbytes = {"flash_fwd": 2 * q + 2 * kv + stats,          # q k v -> o lse
              "flash_dq": 3 * q + 2 * kv + 2 * stats,       # q k v dO -> dQ
              "flash_dkv": 2 * q + 4 * kv + 2 * stats}[kernel]
    return flops, nbytes


def grouped_cost(rows: int, sz: Sizes, itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) the three grouped products of equation 7 need for
    `rows` (token, choice) pairs routed to held experts, forward: 2 FLOPs a
    multiply-accumulate; the rows read, the intermediate written and read,
    the result written, and every held expert's matrices read once. The
    backward pass costs twice this."""
    flops = 2 * rows * 3 * sz.hidden * sz.expert_width
    nbytes = itemsize * (2 * rows * sz.hidden + 4 * rows * sz.expert_width
                         + sz.experts_held * 3 * sz.hidden * sz.expert_width)
    return flops, nbytes
