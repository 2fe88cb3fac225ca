"""Plain float32 reference for the benchmark's second language-model
configuration: JoyAI-LLM-Flash (https://huggingface.co/jdopensource/
JoyAI-LLM-Flash/blob/main/config.json; its keys are the DeepSeek-V3
family's, arXiv:2412.19437; latent attention arXiv:2405.04434), forward
pass, loss and, through `jax.grad`, gradients.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: plain `jax.numpy`, float32, every matrix product
under `jax.default_matmul_precision("highest")`, no kernels, attention by an
explicit mask, the experts by a loop over the experts held. It knows the
program only through `from_net`, which reads the program's freshly
initialised arrays out of Caffe's blob layouts (`[out, in]` for a product).

The layer equations; names are config.json's keys. Hidden D = hidden_size,
no biases; rms(x; g) = x / sqrt(mean(x^2, -1) + rms_norm_eps) * g in float32.

Latent attention, every block. a = rms(h; g1).
1. c_q = rms(a W_dq; g_q), W_dq (D, q_lora_rank); q = c_q W_uq -> H heads of
   qk_nope_head_dim + qk_rope_head_dim = [q_n | q_r].
2. [c_kv | k_r] = a W_dkv, W_dkv (D, kv_lora_rank + qk_rope_head_dim);
   c_kv = rms(c_kv; g_kv); [k_n | v] = c_kv W_ukv -> H heads of
   qk_nope_head_dim + v_head_dim. k_r is ONE head that all H query heads
   share.
3. Rotary on q_r and k_r only (`rope_interleave`): adjacent pairs (x_2i,
   x_2i+1) turned by pos * rope_theta^(-2i / qk_rope_head_dim), positions
   0..S-1, `rope_scaling` null. (The public code re-orders the pairs into
   halves afterwards, the same permutation on q_r and k_r, which leaves
   every score as it is.)
4. score_ij = (q_n,i . k_n,j + q_r,i . k_r,j) / sqrt(nope + rope), causal
   (j <= i), softmax in float32; o = concat_h(P v_h) W_o; u = h + o.

Feed-forward. m = rms(u; g2).
5. The first `first_k_dense_replace` blocks: out = u + (silu(m G) * (m U)) D,
   width intermediate_size.
6. The others: s = sigmoid(m W_r) in float32, W_r (D, n_routed_experts of
   the whole model); I = the num_experts_per_tok largest of s + b (b =
   `e_score_correction_bias`; n_group 1 and topk_group 1: no group limit);
   w = routed_scaling_factor * s[I] / (sum s[I] + 1e-20): the bias selects
   and does not weigh. Under jax.grad s, I and w are constants (the recipe
   trains neither the router nor anything through it).
7. f_e(m) = (silu(m G_e) * (m U_e)) D_e, width moe_intermediate_size; out =
   u + sum over the e in I THAT THIS CHIP HOLDS of w_e f_e(m) + f_shared(m),
   n_shared_experts shared units in one of n_shared_experts times the
   width. What absent experts would add is left out; w is not renormalised
   over the held ones.

Head and multi-token prediction (num_nextn_predict_layers 1; DeepSeek-V3
section 2.2). x = the trunk's output, before the last norm.
8. logits = rms(x; g_f) W_head over this chip's slice of the vocabulary;
   L_main = mean cross-entropy against t_i+1.
9. z = [rms(Emb(t_i+1); g_e) | rms(x_i; g_h)] W_eh, W_eh (2 D, D); z' = one
   more block of equations 1-4 and 6-7, its own weights; logits' = rms(z';
   g_s) W_head with Emb and W_head the trunk's own; L_mtp = mean
   cross-entropy against t_i+2. Loss = L_main + mtp_loss_weight * L_mtp.
   Embedding and head are untied.

Departures from the published model are the configuration file's `assumed`
list (benchmarks/configs/joyai_llm_flash.json).

For the chip, `hidden` computes attention in blocks of queries and
`logits_block` the head in blocks of the vocabulary, so that S = 8192 fits;
`loss_blocked` is `loss` in such blocks, with layers, query blocks, experts
and vocabulary blocks computed again in the backward pass, so that
`jax.grad` of it fits there too. `operand_dtype` rounds both operands of
every matrix product to a narrower type first: the reading "one precision
below the configuration's" that a tolerance has to fail. `rotary`
("part", the model's; "none"; "whole": over all of a head's lanes),
`positions` and a replaced `route` are faults the controls plant
(drivers/train_mla_lm.py).

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

# what does not depend on the architecture is the other reference's: the
# precision wrapper, the norm, the mask's tile and the grouped products'
# count (which reads `hidden`, `expert_width` and `experts_held` of any
# sizes)
from reference.lm_ref import TILE, _highest, grouped_cost, rms  # noqa: F401


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int             # blocks of the trunk, the dense ones included
    dense_layers: int       # the leading blocks whose feed-forward is dense
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rot: int
    v_dim: int
    rope_theta: float
    dense_width: int
    experts: int            # the router's width: every expert of the model
    experts_held: int       # of which this chip holds these,
    first_expert: int       # starting here
    top_k: int
    expert_width: int
    shared_experts: int
    scaling: float
    eps: float
    mtp_weight: float


def sizes_from_config(config: dict, preset: dict | None = None) -> Sizes:
    """From a configuration file's keys (the published config.json's own
    names); a rehearsal preset's `sizes` overrides them."""
    c = {**config, **(preset or {}).get("sizes", {})}
    if c["num_nextn_predict_layers"] != 1 or c["n_group"] != 1:
        raise ValueError("the reference has one MTP module and no expert "
                         "groups")
    return Sizes(
        vocab=c["vocab_size"], hidden=c["hidden_size"],
        layers=c["num_hidden_layers"],
        dense_layers=c["first_k_dense_replace"],
        heads=c["num_attention_heads"], q_lora=c["q_lora_rank"],
        kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
        rot=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        dense_width=c["intermediate_size"],
        experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], first_expert=c["first_expert"],
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["n_shared_experts"],
        scaling=float(c["routed_scaling_factor"]), eps=c["rms_norm_eps"],
        mtp_weight=float(c["mtp_loss_weight"]))


def sizes_record(sz: Sizes) -> dict:
    return dataclasses.asdict(sz)


def sizes_from_record(record: dict) -> Sizes:
    return Sizes(**record)


def _round(x, dt):
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(a, b, dt=None):
    return jnp.matmul(_round(a, dt), _round(b, dt))


def rotate_pairs(x, theta):
    """(N, S, heads, d) -> the same, every adjacent pair (x_2i, x_2i+1) of
    the last axis turned by pos * theta^(-2i/d), positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * inv[None, :])[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return turned.reshape(x.shape)


def attention(lp, a, sz: Sizes, q_block, dt, rotary="part"):
    """Equations 1-4 without the residual."""
    n, s, _ = a.shape
    c_q = rms(_mm(a, lp["w_dq"], dt), lp["g_q"], sz.eps)
    q = _mm(c_q, lp["w_uq"], dt).reshape(n, s, sz.heads, sz.nope + sz.rot)
    down = _mm(a, lp["w_dkv"], dt)
    c_kv = rms(down[..., :sz.kv_lora], lp["g_kv"], sz.eps)
    k_r = down[..., None, sz.kv_lora:]                   # one head
    kv = _mm(c_kv, lp["w_ukv"], dt).reshape(n, s, sz.heads,
                                            sz.nope + sz.v_dim)
    q_n, q_r = q[..., :sz.nope], q[..., sz.nope:]
    k_n, v = kv[..., :sz.nope], kv[..., sz.nope:]
    turn = lambda x: rotate_pairs(x, sz.rope_theta)
    if rotary == "part":
        q_r, k_r = turn(q_r), turn(k_r)
    k_r = jnp.broadcast_to(k_r, q_r.shape)     # under every query head
    if rotary == "whole":   # a planted fault: every lane of the head
        q_n, q_r = jnp.split(turn(jnp.concatenate([q_n, q_r], -1)),
                             [sz.nope], axis=-1)
        k_n, k_r = jnp.split(turn(jnp.concatenate([k_n, k_r], -1)),
                             [sz.nope], axis=-1)
    elif rotary not in ("part", "none"):
        raise ValueError(rotary)
    q_n, q_r, k_n, k_r, v = (_round(x, dt) for x in (q_n, q_r, k_n, k_r, v))
    cols = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(q_block)
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, start, q_block,
                                                     axis=1)
        scores = (jnp.einsum("nqhd,nkhd->nhqk", cut(q_n), k_n)
                  + jnp.einsum("nqhd,nkhd->nhqk", cut(q_r), k_r)) \
            / math.sqrt(sz.nope + sz.rot)
        scores = jnp.where((cols[None, :] <= rows[:, None])[None, None],
                           scores, -jnp.inf)
        p = _round(jax.nn.softmax(scores, axis=-1), dt)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    q_block = min(q_block or s, s)
    if s % q_block:
        raise ValueError(f"query block {q_block} does not divide {s}")
    # under jax.grad a block's scores are computed again, not kept
    o = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    o = jnp.moveaxis(o, 0, 1).reshape(n, s, sz.heads * sz.v_dim)
    return _mm(o, lp["w_o"], dt)


def route(scores, bias, sz: Sizes):
    """Equation 6: (indices (.., k), weights (.., k))."""
    _, idx = jax.lax.top_k(scores + bias, sz.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, sz.scaling * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def gated(m, gate, up, down, dt):
    return _mm(jax.nn.silu(_mm(m, gate, dt)) * _mm(m, up, dt), down, dt)


def routed(lp, m, sz: Sizes, dt, first_expert=None, held=None):
    """The routed part of equation 7: what experts first_expert ..
    first_expert + held - 1 give. `lp["gate"|"up"|"down"]` hold those
    experts' matrices."""
    first = sz.first_expert if first_expert is None else first_expert
    held = sz.experts_held if held is None else held
    scores = jax.nn.sigmoid(_mm(m, lp["router"], dt))
    idx, w = jax.lax.stop_gradient(route(scores, lp["bias"], sz))

    @jax.checkpoint   # under jax.grad: one expert's intermediates at a time
    def add(y, expert):
        e, gate, up, down = expert
        chosen = idx == first + e                          # (.., k)
        w_e = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1)  # 0 if not chosen
        return y + jnp.where(jnp.any(chosen, -1)[..., None],
                             w_e[..., None] * gated(m, gate, up, down, dt),
                             0.0), None
    # a loop over the held experts, one after the other, each over every
    # token and masked to the tokens that chose it
    banks = (jnp.arange(held), lp["gate"][:held], lp["up"][:held],
             lp["down"][:held])
    return jax.lax.scan(add, jnp.zeros_like(m), banks)[0]


def feed_forward(lp, m, sz: Sizes, dt):
    if "router" not in lp:
        return gated(m, lp["gate"], lp["up"], lp["down"], dt)
    out = routed(lp, m, sz, dt)
    if sz.shared_experts:
        out = out + gated(m, lp["s_gate"], lp["s_up"], lp["s_down"], dt)
    return out


def layer(lp, h, sz: Sizes, q_block=None, dt=None, rotary="part"):
    u = h + attention(lp, rms(h, lp["g1"], sz.eps), sz, q_block, dt, rotary)
    return u + feed_forward(lp, rms(u, lp["g2"], sz.eps), sz, dt)


@_highest
def hidden(params, tokens, next_tokens, sz: Sizes, q_block=None,
           operand_dtype=None, remat=False, rotary="part"):
    """(N, S) token ids and the next tokens' -> ((N, S, D), (N, S, D)):
    what the head reads for the main prediction (equation 8's rms(x; g_f))
    and for the MTP module's (equation 9's rms(z'; g_s)). `remat`: under
    jax.grad keep only each layer's input and compute the layer again in
    the backward pass (the timed size on the chip)."""
    step = functools.partial(layer, sz=sz, q_block=q_block,
                             dt=operand_dtype, rotary=rotary)
    step = jax.checkpoint(step) if remat else step
    embed = lambda t: jnp.take(params["embed"], t.astype(jnp.int32), axis=0)
    x = embed(tokens)
    for lp in params["layers"]:
        x = step(lp, x)
    mtp = params["mtp"]
    z = _mm(jnp.concatenate([rms(embed(next_tokens), mtp["g_e"], sz.eps),
                             rms(x, mtp["g_h"], sz.eps)], axis=-1),
            mtp["w_eh"], operand_dtype)
    return (rms(x, params["g_f"], sz.eps),
            rms(step(mtp["block"], z), mtp["g_s"], sz.eps))


@_highest
def logits_block(params, x, lo: int, hi: int, operand_dtype=None):
    """Logits of vocabulary rows lo..hi-1 of this chip's slice."""
    return _mm(x, params["head"][:, lo:hi], operand_dtype)


def forward(params, tokens, next_tokens, sz: Sizes, q_block=None,
            operand_dtype=None):
    """(main logits, MTP logits)."""
    return tuple(logits_block(params, x, 0, sz.vocab, operand_dtype)
                 for x in hidden(params, tokens, next_tokens, sz, q_block,
                                 operand_dtype))


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1))


def loss(params, tokens, labels, labels_mtp, sz: Sizes, q_block=None):
    """L_main + mtp_loss_weight * L_mtp; `labels` are the next tokens, which
    the MTP module also embeds."""
    main, mtp = forward(params, tokens, labels, sz, q_block)
    return cross_entropy(main, labels) \
        + sz.mtp_weight * cross_entropy(mtp, labels_mtp)


@_highest
def loss_blocked(params, tokens, labels, labels_mtp, sz: Sizes, q_block,
                 vocab_block, operand_dtype=None, positions=None,
                 rotary="part"):
    """`loss` for the chip at the timed size, where the (tokens, vocabulary)
    logits may not be held whole, least of all under jax.grad: the
    log-sum-exp from blocks of the vocabulary, each computed again in the
    backward pass, and the label's logit as a row-wise product. The same
    number as `loss`. `positions` (a planted fault for the controls): the
    means over the first so many positions of each sequence only."""
    head = params["head"]                                  # (D, V)
    block = min(vocab_block, sz.vocab)
    n_blocks = -(-sz.vocab // block)
    padded = jnp.pad(head, ((0, 0), (0, n_blocks * block - sz.vocab)))

    def entropy(x, labels):
        if positions is not None:
            x, labels = x[:, :positions], labels[:, :positions]
        x = x.reshape(-1, x.shape[-1])
        labels = labels.astype(jnp.int32).reshape(-1)

        @jax.checkpoint
        def lse_block(i):
            logits = _mm(x, jax.lax.dynamic_slice_in_dim(
                padded, i * block, block, axis=1), operand_dtype)
            live = i * block + jnp.arange(block) < sz.vocab
            return jax.nn.logsumexp(
                jnp.where(live[None, :], logits, -jnp.inf), axis=-1)
        lse = jax.nn.logsumexp(
            jax.lax.map(lse_block, jnp.arange(n_blocks)), axis=0)
        w = jnp.take(head, labels, axis=1).T               # (tokens, D)
        return jnp.mean(lse - jnp.sum(_round(x, operand_dtype)
                                      * _round(w, operand_dtype), axis=-1))

    main, mtp = hidden(params, tokens, labels, sz, q_block, operand_dtype,
                       remat=True, rotary=rotary)
    return entropy(main, labels) + sz.mtp_weight * entropy(mtp, labels_mtp)


def from_net(net_params: dict, sz: Sizes) -> dict:
    """The reference's weights out of the program's blobs (the prototxt
    `models/generate_models.py joyai_llm_flash` emits). `Embed.weight` (V,
    D); `Attention` q_a_weight (q_lora, D), q_norm, q_b_weight (H (nope +
    rope), q_lora), kv_a_weight (kv_lora + rope, D), kv_norm, kv_b_weight
    (H (nope + v), kv_lora; a head's key rows, then its value rows),
    proj_weight (D, H v), all [out, in]; `RMSNorm.scale`; the dense block's
    three `InnerProduct.weight`; `MoE` gate (D, E), select_bias (E), w1 =
    G, w3 = U (held, D, W), w2 = D (held, W, D), shared_w1 / shared_w3 (D,
    Ws), shared_w2 (Ws, D); eh_proj's and the head's `InnerProduct.weight`.
    The MTP module's table and head are the trunk's. A linear map, so it
    carries gradients the same way."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)

    def block(b: str, dense: bool) -> dict:
        attn = net_params[f"{b}/attn"]
        out = {"g1": f32(net_params[f"{b}/ln1"]["scale"]),
               "w_dq": f32(attn["q_a_weight"]).T, "g_q": f32(attn["q_norm"]),
               "w_uq": f32(attn["q_b_weight"]).T,
               "w_dkv": f32(attn["kv_a_weight"]).T,
               "g_kv": f32(attn["kv_norm"]),
               "w_ukv": f32(attn["kv_b_weight"]).T,
               "w_o": f32(attn["proj_weight"]).T,
               "g2": f32(net_params[f"{b}/ln2"]["scale"])}
        if dense:
            return {**out, **{k: f32(net_params[f"{b}/{k}"]["weight"]).T
                              for k in ("gate", "up", "down")}}
        moe = net_params[f"{b}/moe"]
        return {**out, "router": f32(moe["gate"]),
                "bias": f32(moe["select_bias"]), "gate": f32(moe["w1"]),
                "up": f32(moe["w3"]), "down": f32(moe["w2"]),
                "s_gate": f32(moe["shared_w1"]), "s_up": f32(moe["shared_w3"]),
                "s_down": f32(moe["shared_w2"])}
    return {"embed": f32(net_params["embed"]["weight"]),
            "layers": [block(f"blk{l}", l < sz.dense_layers)
                       for l in range(sz.layers)],
            "g_f": f32(net_params["ln_f"]["scale"]),
            "head": f32(net_params["logits"]["weight"]).T,
            "mtp": {"g_e": f32(net_params["mtp/enorm"]["scale"]),
                    "g_h": f32(net_params["mtp/hnorm"]["scale"]),
                    "w_eh": f32(net_params["mtp/eh_proj"]["weight"]).T,
                    "block": block("mtp", False),
                    "g_s": f32(net_params["mtp/ln_f"]["scale"])}}


# -- counts, from shapes alone -----------------------------------------------

def attention_weights(sz: Sizes) -> int:
    """Elements of equation 1-4's five matrices."""
    return (sz.hidden * sz.q_lora + sz.q_lora * sz.heads * (sz.nope + sz.rot)
            + sz.hidden * (sz.kv_lora + sz.rot)
            + sz.kv_lora * sz.heads * (sz.nope + sz.v_dim)
            + sz.heads * sz.v_dim * sz.hidden)


def param_count(sz: Sizes) -> int:
    block = attention_weights(sz) + sz.q_lora + sz.kv_lora + 2 * sz.hidden
    unit = 3 * sz.hidden * sz.expert_width
    expert_block = (block + sz.hidden * sz.experts + sz.experts
                    + (sz.experts_held + sz.shared_experts) * unit)
    dense_block = block + 3 * sz.hidden * sz.dense_width
    mtp = 2 * sz.hidden + 2 * sz.hidden * sz.hidden + expert_block + sz.hidden
    return (sz.dense_layers * dense_block
            + (sz.layers - sz.dense_layers) * expert_block
            + 2 * sz.vocab * sz.hidden + sz.hidden + mtp)


def visible_pairs(seq: int) -> int:
    """(query, key) pairs of one head the causal mask leaves."""
    return seq * (seq + 1) // 2


def visible_tiles(seq: int, tile: int = TILE) -> int:
    """Tiles of `tile` x `tile` (query, key) pairs that hold at least one
    visible pair: what a tiled kernel cannot avoid visiting."""
    n = -(-seq // tile)
    return n * (n + 1) // 2


def macs_per_sample(sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates of one sequence of `seq` tokens: the
    projections, scores over nope + rope lanes and values over v lanes of
    the visible pairs only, the router, the shared experts, the held
    experts at their expected top_k * held / experts rows a token, the
    dense feed-forward, the MTP module's input product and both heads. The
    embeddings are gathers."""
    attention = seq * attention_weights(sz) + visible_pairs(seq) \
        * sz.heads * (sz.nope + sz.rot + sz.v_dim)
    unit = 3 * sz.hidden * sz.expert_width
    experts = seq * (sz.hidden * sz.experts + sz.shared_experts * unit) \
        + seq * sz.top_k * sz.experts_held * unit // sz.experts
    dense = seq * 3 * sz.hidden * sz.dense_width
    blocks = (sz.layers + 1) * attention + sz.dense_layers * dense \
        + (sz.layers - sz.dense_layers + 1) * experts
    return blocks + seq * 2 * sz.hidden * sz.hidden \
        + 2 * seq * sz.hidden * sz.vocab


def flash_cost(kernel: str, sz: Sizes, batch: int, seq: int,
               itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one call of a flash kernel needs: the matrix products
    over the visible 128 x 128 tiles, 2 FLOPs a multiply-accumulate — QK^T
    and its two backward uses (dS K, dS^T Q) over nope + rope lanes, PV and
    its two (dO V^T, P^T dO) over v lanes; the forward QK^T and PV, dQ QK^T
    again, dO V^T and dS K, dK/dV QK^T again, P^T dO, dO V^T and dS^T Q —
    and each operand read and each result written once: q and dQ at nope +
    rope lanes a head, the keys' nope lanes a head and their rotary lanes
    once for all heads, v, o, dO, dV at v lanes a head, the float32 row
    statistics."""
    qk, v = sz.nope + sz.rot, sz.v_dim
    per_pair = {"flash_fwd": 2 * (qk + v),
                "flash_dq": 2 * (2 * qk + v),
                "flash_dkv": 2 * (2 * qk + 2 * v)}[kernel]
    flops = per_pair * visible_tiles(seq) * TILE * TILE * sz.heads * batch
    rows = batch * seq * itemsize
    q, o = rows * sz.heads * qk, rows * sz.heads * v
    k = rows * (sz.heads * sz.nope + sz.rot)
    stats = batch * seq * sz.heads * 4
    nbytes = {"flash_fwd": q + k + 2 * o + stats,          # q k v -> o lse
              "flash_dq": 2 * q + k + 2 * o + 2 * stats,   # q k v dO -> dQ
              "flash_dkv": q + 2 * k + 3 * o + 2 * stats}[kernel]
    return flops, nbytes
