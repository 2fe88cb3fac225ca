"""Plain float32 reference for the benchmark's block-diffusion configuration:
SDAR-30B-A3B-Chat (https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/
config.json, `model_type: sdar_moe`; SDAR, arXiv:2510.06303; block diffusion,
arXiv:2503.09573), its training step: forward pass over the noisy sequence
beside the clean one, the 1/t-weighted masked loss and, through `jax.grad`,
gradients.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: plain `jax.numpy`, float32, every matrix product
under `jax.default_matmul_precision("highest")`, no kernels, attention by an
explicit dense mask, the experts by a loop over the experts held. It knows
the program only through `from_net`, which reads the program's freshly
initialised arrays out of Caffe's blob layouts (`[out, in]` for a product).

Block diffusion over one sequence x_0 of L tokens in blocks of B: block b
draws t_b ~ U(t_min, 1) and masks each of its tokens independently with
probability t_b; x_t[i] = MASK there, x_0[i] elsewhere. The net reads
`ids` = [x_t | x_0], 2 L rows; row i sits at position pos(i) = i mod L in
block blk(i) = (i mod L) // B. Row i sees column j iff

- i, j < L (noisy on noisy): blk(i) == blk(j);
- i < L <= j (noisy on clean): blk(j) < blk(i);
- i, j >= L (clean on clean): blk(j) <= blk(i);
- i >= L > j (clean on noisy): never.

A layer (pre-norm; h (N, 2 L, D)):

1. a = rms(h) * g1, rms(x) = x / sqrt(mean(x^2, -1) + eps);
2. q = a W_q -> (N, 2 L, H, d); k = a W_k, v = a W_v -> (N, 2 L, Hkv, d);
   no biases;
3. q_n = rms(q_n) * g_q and k_m = rms(k_m) * g_k, over the d lanes of each
   head, the same eps; then both rotated over all d lanes, rotate-half
   convention, AT POSITION pos(i), no scaling;
4. query head n attends key/value head n // (H / Hkv), scale 1 / sqrt(d),
   under the mask above, softmax in float32. u = h + concat(o) W_o;
5. m = rms(u) * g2; router logits r = m W_r, W_r (D, E); under jax.grad r
   is a constant (the recipe trains neither the router nor anything
   through it);
6. per token: p = softmax(r) over all E experts, I = its top_k largest,
   w = p[I] / sum p[I] (`norm_topk_prob`);
7. expert e: f_e(m) = (silu(m G_e) * (m U_e)) D_e, no biases;
8. out = u + sum over the e in I THAT THIS CHIP HOLDS of w_e f_e(m). What
   the absent experts would add is left out; w is not renormalised.

After the last layer the noisy half alone: rms * g, logits = x W_head over
this chip's slice of the vocabulary, and with no shift (the logit at a
masked position predicts that position's token)

    loss = (1 / (N L)) sum over masked i of (1 / t_blk(i)) CE(logits_i, x_0[i]).

The noise is the program's to draw (`BlockDiffusionNoise`); the reference
takes the draw as the layer's tops give it (`ids`, `labels`, `weights`) and
`noise_faults` holds it to the definition above. Departures from the
published model are the configuration file's `assumed` list
(benchmarks/configs/sdar_30b_a3b.json).

For the chip, `hidden` computes attention in blocks of queries and
`loss_blocked` the head in blocks of the vocabulary, with layers, query
blocks, experts and vocabulary blocks computed again in the backward pass,
so that `jax.grad` fits beside the weights. `operand_dtype` rounds both
operands of every matrix product to a narrower type first; the other
keywords of `hidden` plant one fault each (a wrong mask, wrong positions, no
q/k norm) for the controls that the limits are set from (PERF.md section 2).

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
    rope_theta: float
    experts: int            # the router's width: every expert of the model
    experts_held: int       # of which this chip holds these,
    first_expert: int       # starting here
    top_k: int
    expert_width: int
    eps: float
    block_length: int
    mask_id: int
    t_min: float
    ignore_label: int


def sizes_from_config(config: dict, preset: dict | None = None) -> Sizes:
    """From a configuration file's keys (the published config.json's own
    names, and `block_diffusion` for what it has no key for); a rehearsal
    preset's `sizes` overrides them."""
    c = {**config, **(preset or {}).get("sizes", {})}
    bd = c["block_diffusion"]
    return Sizes(
        vocab=c["vocab_size"], hidden=c["hidden_size"],
        layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]),
        experts=c["published"]["num_experts"],
        experts_held=c["num_experts"], first_expert=c["first_expert"],
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"], eps=c["rms_norm_eps"],
        block_length=bd["block_length"], mask_id=bd["mask_id"],
        t_min=bd["t_min"], ignore_label=bd["ignore_label"])


def sizes_record(sz: Sizes) -> dict:
    return dataclasses.asdict(sz)


def sizes_from_record(record: dict) -> Sizes:
    return Sizes(**record)


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


def rotate(x, theta, positions):
    """(N, S, heads, d) -> the same, row i turned to `positions[i]`,
    rotate-half: x cos + [-x2, x1] sin, angle_i = pos / theta^(2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


# the masks the controls plant in the sound one's place
MASKS = ("block_diffusion", "causal", "noisy_sees_own_clean",
         "clean_token_causal", "noisy_token_causal")


def visible(rows, cols, half: int, block: int, mask: str = MASKS[0]):
    """The mask of the four cases above for rows `rows` against columns
    `cols` (index vectors over the 2 x half sequence). `mask` names a
    planted fault: plain causal over all 2 L; noisy on clean made <= (a
    block sees its own clean tokens); clean on clean by token; noisy on
    noisy causal by token inside the block."""
    i, j = rows[:, None], cols[None, :]
    if mask == "causal":
        return j <= i
    noisy_i, noisy_j = i < half, j < half
    pi, pj = i % half, j % half
    bi, bj = pi // block, pj // block
    on_noisy = bi == bj
    if mask == "noisy_token_causal":
        on_noisy &= pj <= pi
    on_clean = bj <= bi if mask == "noisy_sees_own_clean" else bj < bi
    clean = pj <= pi if mask == "clean_token_causal" else bj <= bi
    return jnp.where(noisy_i, jnp.where(noisy_j, on_noisy, on_clean),
                     ~noisy_j & clean)


def attend(q, k, v, half: int, block: int, q_block, mask=MASKS[0], dt=None):
    """Equation 4's core: q (N, 2 L, H, d) against k, v (N, 2 L, Hkv, d)
    under the mask, in blocks of `q_block` queries; scale 1 / sqrt(d),
    softmax in float32."""
    n, s, heads, d = q.shape
    group = heads // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    cols = jnp.arange(s)
    if dt is not None:
        q, k, v = (x.astype(dt).astype(jnp.float32) for x in (q, k, v))

    def one(start):
        rows = start + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        scores = jnp.einsum("nqhd,nkhd->nhqk", qb, k) / math.sqrt(d)
        seen = visible(rows, cols, half, block, mask)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if dt is not None:
            p = p.astype(dt).astype(jnp.float32)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    q_block = min(q_block or s, s)
    if s % q_block:
        raise ValueError(f"query block {q_block} does not divide {s}")
    # under jax.grad a block's scores are computed again, not kept
    o = jax.lax.map(jax.checkpoint(one), jnp.arange(0, s, q_block))
    return jnp.moveaxis(o, 0, 1).reshape(n, s, heads, v.shape[-1])


def attention(lp, a, sz: Sizes, q_block, dt, mask=MASKS[0],
              positions="per_half", qk_norm=True):
    n, s, _ = a.shape
    half = s // 2
    q = _mm(a, lp["wq"], dt).reshape(n, s, sz.heads, sz.head_dim)
    k = _mm(a, lp["wk"], dt).reshape(n, s, sz.kv_heads, sz.head_dim)
    v = _mm(a, lp["wv"], dt).reshape(n, s, sz.kv_heads, sz.head_dim)
    if qk_norm:
        q, k = rms(q, lp["gq"], sz.eps), rms(k, lp["gk"], sz.eps)
    at = jnp.arange(s) % half if positions == "per_half" else jnp.arange(s)
    q, k = rotate(q, sz.rope_theta, at), rotate(k, sz.rope_theta, at)
    o = attend(q, k, v, half, sz.block_length, q_block, mask, dt)
    return _mm(o.reshape(n, s, sz.heads * sz.head_dim), lp["wo"], dt)


@_highest
def probe_inputs(key, seq: int, sz: Sizes, dtype):
    """Inputs on which the mask decides the result, for `mask_probe`: the
    logits and gradients of the whole net hardly move with what a row sees
    INSIDE its block (4 keys of thousands, near-uniform attention on fresh
    weights), so a token-causal block or a block that sees its own clean
    tokens would pass both of those limits. Here row i's query points at
    the key of position pos(i) + 1: q_i = 2 u[pos(i) + 1], k_j = u[pos(j)],
    u unit normal, so the score is 2 sqrt(d) there and noise of deviation 2
    elsewhere. Under the block mask a noisy row finds that key among the
    noisy ones iff it lies in its block, a clean row among the clean ones;
    token-causal masks hide it, and a noisy row that sees its own clean
    block finds two. Returns q (1, 2 seq, H, d), k, v (1, 2 seq, Hkv, d)
    and a cotangent like q, rounded to `dtype`."""
    ku, kv, kc = jax.random.split(key, 3)
    d, group = sz.head_dim, sz.heads // sz.kv_heads
    u = jax.random.normal(ku, (seq + 1, sz.kv_heads, d), jnp.float32)
    pos = jnp.arange(2 * seq) % seq
    q = 2.0 * jnp.repeat(u[pos + 1], group, axis=1)
    v = jax.random.normal(kv, (2 * seq, sz.kv_heads, d), jnp.float32)
    cot = jax.random.normal(kc, (2 * seq, sz.heads, d), jnp.float32)
    return tuple(x[None].astype(dtype) for x in (q, u[pos], v, cot))


@_highest
def probe_reference(q, k, v, cot, sz: Sizes, q_block, mask=MASKS[0]):
    """(out, dq, dk, dv) of `attend` on the probe's inputs, in float32."""
    q, k, v, cot = (x.astype(jnp.float32) for x in (q, k, v, cot))
    out, vjp = jax.vjp(lambda q, k, v: attend(
        q, k, v, q.shape[1] // 2, sz.block_length, q_block, mask), q, k, v)
    return (out, *vjp(cot))


def route(r, top_k: int):
    """Equation 6: (indices (.., k), weights (.., k))."""
    p, idx = jax.lax.top_k(jax.nn.softmax(r, axis=-1), top_k)
    return idx, p / jnp.sum(p, axis=-1, keepdims=True)


def experts(lp, m, r, sz: Sizes, dt, first_expert=None, held=None):
    """Equations 6-8 without the residual: the part of the expert layer's
    result that experts first_expert .. first_expert + held - 1 give.
    `lp["gate"|"up"|"down"]` hold those experts' matrices."""
    first = sz.first_expert if first_expert is None else first_expert
    held = sz.experts_held if held is None else held
    idx, w = route(r, sz.top_k)

    @jax.checkpoint   # under jax.grad: one expert's intermediates at a time
    def one(m, expert):
        e, gate, up, down = expert
        chosen = idx == first + e                          # (.., k)
        w_e = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1)  # 0 if not chosen
        f = _mm(jax.nn.silu(_mm(m, gate, dt)) * _mm(m, up, dt), down, dt)
        return jnp.where(jnp.any(chosen, -1)[..., None],
                         w_e[..., None] * f, 0.0)
    # a loop over the held experts, one after the other, each over every
    # row and masked to the rows that chose it; the running sum stays
    # outside the checkpoint, so that it is not kept once an expert
    banks = (jnp.arange(held), lp["gate"][:held], lp["up"][:held],
             lp["down"][:held])
    return jax.lax.scan(lambda y, expert: (y + one(m, expert), None),
                        jnp.zeros_like(m), banks)[0]


def layer(lp, h, sz: Sizes, q_block=None, dt=None, **how):
    u = h + attention(lp, rms(h, lp["g1"], sz.eps), sz, q_block, dt, **how)
    m = rms(u, lp["g2"], sz.eps)
    # routing is a constant of the training step, as in the recipe (the
    # configuration's `assumed`): neither the router nor anything through
    # it has a gradient
    r = jax.lax.stop_gradient(_mm(m, lp["router"], dt))
    return u + experts(lp, m, r, sz, dt)


@_highest
def hidden(params, ids, sz: Sizes, q_block=None, operand_dtype=None,
           remat=False, **how):
    """(N, 2 L) ids [x_t | x_0] -> (N, L, D): the noisy half after the last
    layer, the last norm applied. `remat`: under jax.grad keep only each
    layer's input and compute the layer again in the backward pass (the
    timed size on the chip). `how`: a planted fault (`mask`, `positions`,
    `qk_norm`)."""
    h = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)
    for lp in params["layers"]:
        step = functools.partial(layer, sz=sz, q_block=q_block,
                                 dt=operand_dtype, **how)
        h = (jax.checkpoint(step) if remat else step)(lp, h)
    return rms(h[:, :ids.shape[1] // 2], params["g_f"], sz.eps)


@_highest
def logits_block(params, x, lo: int, hi: int, operand_dtype=None):
    """Logits of vocabulary rows lo..hi-1 of this chip's slice."""
    return _mm(x, params["head"][:, lo:hi], operand_dtype)


def forward(params, ids, sz: Sizes, q_block=None, operand_dtype=None,
            **how):
    x = hidden(params, ids, sz, q_block, operand_dtype, **how)
    return logits_block(params, x, 0, sz.vocab, operand_dtype)


LOSSES = ("weighted_masked", "unweighted", "all_positions")


def loss_terms(ids, labels, weights, sz: Sizes, loss: str = LOSSES[0]):
    """(labels, weights) as the loss reads them: the clean token and 1 / t
    at the masked positions, weight 0 elsewhere. `loss` names a planted
    fault: "unweighted" drops the 1 / t (1 where masked); "all_positions"
    counts the unmasked positions too, at weight 1, against their clean
    tokens."""
    masked = labels != sz.ignore_label
    if loss == "unweighted":
        weights = masked.astype(jnp.float32)
    if loss == "all_positions":
        clean = ids[:, ids.shape[1] // 2:].astype(labels.dtype)
        return clean, jnp.where(masked, weights, 1.0)
    return jnp.where(masked, labels, 0), jnp.where(masked, weights, 0.0)


def loss(params, ids, labels, weights, sz: Sizes, q_block=None):
    """sum over masked positions of (1/t) CE, over N L; float32
    log-softmax, no shift."""
    logp = jax.nn.log_softmax(forward(params, ids, sz, q_block), axis=-1)
    labels, weights = loss_terms(ids, labels, weights, sz)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(weights * picked) / labels.size


@_highest
def loss_blocked(params, ids, labels, weights, sz: Sizes, q_block,
                 vocab_block, operand_dtype=None, loss=LOSSES[0], **how):
    """`loss` for the chip at the timed size, where the (tokens, vocabulary)
    logits may not be held whole, least of all under jax.grad: the
    log-sum-exp from blocks of the vocabulary, each computed again in the
    backward pass, and the label's logit as a row-wise product. The same
    number as `loss`. `loss`: a planted fault for the controls
    (`loss_terms`)."""
    x = hidden(params, ids, sz, q_block, operand_dtype, remat=True, **how)
    labels, weights = loss_terms(ids, labels, weights, sz, loss)
    x = x.reshape(-1, x.shape[-1])
    labels, weights = labels.reshape(-1), weights.reshape(-1)
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
    return jnp.sum(weights * (lse - jnp.sum(x * w, axis=-1))) / labels.size


def noise_faults(tokens, ids, labels, weights, sz: Sizes) -> dict:
    """How far one draw of the program's noise layer is from the
    definition: counts of positions (each must be 0) where the clean half is
    not x_0, the noisy half is neither MASK where masked nor x_0 elsewhere,
    a label is not x_0 where masked, a weight is not one value 1 / t over
    the masked positions of its block with t in [t_min, 1], or is not 0
    where not masked; and the masked share of the L positions."""
    n, l = tokens.shape
    b = sz.block_length
    masked = labels != sz.ignore_label
    pad = -l % b
    by_block = lambda a, fill: jnp.pad(a, ((0, 0), (0, pad)),
                                       constant_values=fill).reshape(n, -1, b)
    w_hi = jnp.max(by_block(weights, 0.0), axis=-1, keepdims=True)
    same = jnp.where(by_block(masked, False),
                     by_block(weights, 0.0) != w_hi, False)
    count = lambda bad: int(jnp.sum(bad))
    return {
        "clean_half_is_not_x0": count(ids[:, l:] != tokens),
        "noisy_half_wrong": count(
            ids[:, :l] != jnp.where(masked, sz.mask_id, tokens)),
        "label_is_not_x0": count(masked & (labels != tokens)),
        "weight_not_one_a_block": count(same),
        "weight_out_of_range": count(masked & ~(
            (weights >= 1.0) & (weights <= 1.0 / sz.t_min * (1 + 1e-6)))),
        "weight_where_not_masked": count(~masked & (weights != 0.0)),
        "clean_token_is_mask_id": count(tokens == sz.mask_id),
        "masked_share": float(jnp.mean(masked)),
    }


def from_net(net_params: dict, sz: Sizes) -> dict:
    """The reference's weights out of the program's blobs (the prototxt
    `models/generate_models.py sdar` emits): `Embed.weight` (V, D);
    `Attention.qkv_weight` ((H + 2 Hkv) d, D), rows q then k then v,
    `proj_weight` (D, H d), both [out, in], `q_norm` and `k_norm` (d,);
    `RMSNorm.scale`; `MoE.gate` (D, E), `w1` = G, `w3` = U (held, D, W),
    `w2` = D (held, W, D); `InnerProduct.weight` (V, D). A linear map, so
    it carries gradients the same way."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    layers = []
    for l in range(sz.layers):
        attn = net_params[f"blk{l}/attn"]
        qkv = f32(attn["qkv_weight"])
        moe = net_params[f"blk{l}/moe"]
        layers.append({
            "g1": f32(net_params[f"blk{l}/ln1"]["scale"]),
            "wq": qkv[:nq].T, "wk": qkv[nq:nq + nkv].T,
            "wv": qkv[nq + nkv:].T, "wo": f32(attn["proj_weight"]).T,
            "gq": f32(attn["q_norm"]), "gk": f32(attn["k_norm"]),
            "g2": f32(net_params[f"blk{l}/ln2"]["scale"]),
            "router": f32(moe["gate"]), "gate": f32(moe["w1"]),
            "up": f32(moe["w3"]), "down": f32(moe["w2"])})
    return {"embed": f32(net_params["embed"]["weight"]), "layers": layers,
            "g_f": f32(net_params["ln_f"]["scale"]),
            "head": f32(net_params["logits"]["weight"]).T}


# -- counts, from shapes alone -----------------------------------------------

def param_count(sz: Sizes) -> int:
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    attention = sz.hidden * (nq + 2 * nkv) + nq * sz.hidden + 2 * sz.head_dim
    expert = 3 * sz.hidden * sz.expert_width
    per_layer = (attention + sz.hidden * sz.experts
                 + sz.experts_held * expert + 2 * sz.hidden)
    return sz.layers * per_layer + 2 * sz.vocab * sz.hidden + sz.hidden


def _block_sizes(half: int, block: int) -> list[int]:
    return [min(block, half - lo) for lo in range(0, half, block)]


def visible_pairs(half: int, block: int) -> int:
    """(row, column) pairs of one head the mask leaves over [noisy | clean]:
    a noisy row its own block and the clean blocks before it, a clean row
    the clean blocks up to its own."""
    pairs = before = 0
    for size in _block_sizes(half, block):
        pairs += size * (size + before) + size * (before + size)
        before += size
    return pairs


def visible_tiles(half: int, block: int, tile: int = TILE) -> int:
    """Tiles of `tile` x `tile` (row, column) pairs over the 2 x half
    sequence that hold at least one visible pair BY THE DEFINITION (not the
    ones some kernel visits): what a tiled kernel cannot avoid."""
    blk = lambda i: (i % half) // block
    n = -(-2 * half // tile)
    span = lambda t, lo, hi: (max(t * tile, lo), min((t + 1) * tile, hi) - 1)
    count = 0
    for qi in range(n):
        rn0, rn1 = span(qi, 0, half)              # its noisy rows, if any
        rc0, rc1 = span(qi, half, 2 * half)       # its clean rows
        for kj in range(n):
            cn0, cn1 = span(kj, 0, half)
            cc0, cc1 = span(kj, half, 2 * half)
            count += bool(
                (rn0 <= rn1 and cn0 <= cn1
                 and blk(rn0) <= blk(cn1) and blk(cn0) <= blk(rn1))
                or (rn0 <= rn1 and cc0 <= cc1 and blk(cc0) < blk(rn1))
                or (rc0 <= rc1 and cc0 <= cc1 and blk(cc0) <= blk(rc1)))
    return count


def macs_per_sample(sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates of one sample, a sequence of `seq`
    clean tokens: 2 x seq rows through every layer (projections, scores
    and values over the visible pairs only, the router, the held experts at
    their expected top_k * held / experts rows a row), the head over the
    noisy half. The embedding is a gather."""
    rows = 2 * seq
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    projections = rows * (sz.hidden * (nq + 2 * nkv) + nq * sz.hidden)
    router = rows * sz.hidden * sz.experts
    held = rows * sz.top_k * sz.experts_held * 3 * sz.hidden \
        * sz.expert_width // sz.experts
    scores = 2 * visible_pairs(seq, sz.block_length) * nq
    return (sz.layers * (projections + router + held + scores)
            + seq * sz.hidden * sz.vocab)


# FLOPs a (query, key) pair of one head costs each flash kernel, in units
# of the head size d: forward QK^T and PV; dQ recomputes QK^T, then dO V^T
# and dS K; dK/dV recomputes QK^T, then P^T dO, dO V^T and dS^T Q
FLASH_FLOPS_PER_PAIR = {"flash_fwd": 4, "flash_dq": 6, "flash_dkv": 8}


@functools.lru_cache(maxsize=None)
def flash_cost(kernel: str, sz: Sizes, batch: int, seq: int,
               itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one call of a flash kernel needs in a layer, over the
    2 x seq rows: the matrix products over the 128 x 128 tiles the
    block-diffusion mask leaves, and each operand read and each result
    written once (q, o, dO and dQ over the query heads, k, v, dK and dV
    over the key/value heads, the float32 row statistics)."""
    rows = 2 * seq
    pairs = visible_tiles(seq, sz.block_length) * TILE * TILE
    flops = (FLASH_FLOPS_PER_PAIR[kernel] * sz.head_dim * pairs
             * sz.heads * batch)
    q = batch * rows * sz.heads * sz.head_dim * itemsize
    kv = batch * rows * sz.kv_heads * sz.head_dim * itemsize
    stats = batch * rows * sz.heads * 4
    nbytes = {"flash_fwd": 2 * q + 2 * kv + stats,          # q k v -> o lse
              "flash_dq": 3 * q + 2 * kv + 2 * stats,       # q k v dO -> dQ
              "flash_dkv": 2 * q + 4 * kv + 2 * stats}[kernel]
    return flops, nbytes


def grouped_cost(rows: int, sz: Sizes, itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) the three grouped products of equation 7 need for
    `rows` (row, choice) pairs routed to held experts, forward: 2 FLOPs a
    multiply-accumulate; the rows read, the intermediate written and read,
    the result written, and every held expert's matrices read once. The
    backward pass costs twice this."""
    flops = 2 * rows * 3 * sz.hidden * sz.expert_width
    nbytes = itemsize * (2 * rows * sz.hidden + 4 * rows * sz.expert_width
                         + sz.experts_held * 3 * sz.hidden * sz.expert_width)
    return flops, nbytes
