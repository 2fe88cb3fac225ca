"""Plain float32 reference for the benchmark's language-model configuration
with manifold-constrained hyper-connections: Xing4.0-29B-A4B
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json;
the DeepSeek-V3 block, arXiv:2412.19437, latent attention arXiv:2405.04434,
YaRN arXiv:2309.00071; its residual is mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606), forward pass, loss and, through
`jax.grad`, gradients.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: plain `jax.numpy`, float32, every matrix product
under `jax.default_matmul_precision("highest")`, no kernels, attention by an
explicit mask, the experts by a loop over the experts held. It knows the
program only through `from_net`, which reads the program's freshly
initialised arrays out of Caffe's blob layouts (`[out, in]` for a product).
What it shares with `joyai_ref` is that reference's own: the norm, the
router and the expert units (equations 6-7 there), which are the same
block's.

The equations; names are config.json's keys. Hidden C = hidden_size, n =
hc_mult, rms(x; g) = x / sqrt(mean(x^2, -1) + rms_norm_eps) * g in float32.

Residual streams (mHC). The residual is x in R^(n x C) a token.
1. x_0 = the token's embedding in each of the n streams.
2. Around each sub-layer F (attention with its norm, the feed-forward with
   its norm): x' = rms(vec(x); 1) over the n C lanes;
   H_pre = sigmoid(a_pre x' phi_pre + b_pre) (1 x n);
   H_post = 2 sigmoid(a_post x' phi_post + b_post) (1 x n);
   H_res = SK(exp(clip(a_res mat(x' phi_res) + b_res, mhc_h_res_clamp_min,
   mhc_h_res_clamp_max))) (n x n), SK = hc_sinkhorn_iters times {each row
   divided by its sum + hc_eps; each column divided by its sum + hc_eps};
   x <- H_res x + H_post^T F(H_pre x): stream i becomes
   sum_j H_res[i, j] x_j + H_post[i] F.
3. The streams summed before the last norm.

Latent attention, F of the first sub-layer. a = rms(h; g1).
4. c_q = rms(a W_dq; g_q); q = c_q W_uq -> heads of qk_nope_head_dim +
   qk_rope_head_dim = [q_n | q_r]; [c_kv | k_r] = a W_dkv; c_kv = rms(c_kv;
   g_kv); [k_n | v] = c_kv W_ukv. k_r is one head all query heads share.
5. Rotary on q_r and k_r only, adjacent pairs (x_2i, x_2i+1) turned by pos *
   inv_freq_i, YaRN's (`rope_scaling`): with f_i = rope_theta^(-2i/d), d =
   qk_rope_head_dim, inv_freq_i = f_i / factor * (1 - m_i) + f_i m_i, m_i =
   1 - clip((i - low) / (high - low), 0, 1), low = max(floor(c(beta_fast)),
   0), high = min(ceil(c(beta_slow)), d - 1), c(b) = d ln(L / (2 pi b)) /
   (2 ln rope_theta), L = original_max_position_embeddings; cos and sin
   times mscale(mscale) / mscale(mscale_all_dim), mscale(m) = 0.1 m
   ln(factor) + 1.
6. score_ij = (q_n,i . k_n,j + q_r,i . k_r,j) mscale(mscale_all_dim)^2 /
   sqrt(nope + rope), causal, softmax in float32; o = concat_h(P v_h) W_o.

Feed-forward, F of the second sub-layer. m = rms(h; g2).
7. The first `first_k_dense_replace` layers: (silu(m G) * (m U)) D.
8. The others: joyai_ref's equations 6-7 (sigmoid scores, the top
   num_experts_per_tok of s + e_score_correction_bias, w =
   routed_scaling_factor * s[I] / sum s[I], the held experts' part plus the
   shared expert's).

Head: logits = rms(sum of the streams; g_f) W_head over this chip's slice
of the vocabulary; the loss is the mean cross-entropy against t_i+1.

Departures from the published model are the configuration file's `assumed`
and `reduced` (benchmarks/configs/xing4_29b_a4b.json): no MTP module.

For the chip, `hidden` computes attention in blocks of queries and
`loss_blocked` the head in blocks of the vocabulary, with every layer
computed again in the backward pass. The faults the controls plant
(drivers/train_mhc_lm.py): `operand_dtype` rounds both operands of every
matrix product; `fault` holds a 0 or a 1 for each of `FAULTS`, as data, so
that one compiled program serves them all: SK's first iteration in place
of its last, H_res^T x, H_post without its 2, one stream (x + F(x)), H_pre
fixed at 1/n, rope_theta's own rotary in place of YaRN's, scores by 1 /
sqrt(nope + rope), and the stream rounded to an 8-bit float after every
update.

The counting functions at the end (`macs_per_sample`, `param_count`,
`flash_cost`, `mhc_cost`) are the benchmark's own count of what the
algorithm needs, from shapes alone; mfu and the roofline shares read them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from reference.joyai_ref import (_mm, _round, cross_entropy, feed_forward,
                                 flash_cost, visible_pairs)  # noqa: F401
from reference.lm_ref import _highest, rms


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    layers: int             # blocks, the dense ones included
    dense_layers: int       # the leading blocks whose feed-forward is dense
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rot: int
    v_dim: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    dense_width: int
    experts: int            # the router's width: every expert of the model
    experts_held: int       # of which this chip holds these,
    first_expert: int       # starting here
    top_k: int
    expert_width: int
    shared_experts: int
    scaling: float
    eps: float
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    clamp: float
    # the weight of an MTP module's loss: none here (num_nextn_predict_layers
    # is reduced to 0)
    mtp_weight: float = 0.0


def sizes_from_config(config: dict, preset: dict | None = None) -> Sizes:
    """From a configuration file's keys (the published config.json's own
    names); a rehearsal preset's `sizes` overrides them."""
    c = {**config, **(preset or {}).get("sizes", {})}
    rs = c["rope_scaling"]
    if c["num_nextn_predict_layers"] != 0 or c["n_group"] != 1 \
            or rs["type"] != "yarn":
        raise ValueError("the reference has no MTP module, no expert "
                         "groups, and YaRN scaling")
    if -c["mhc_h_res_clamp_min"] != c["mhc_h_res_clamp_max"]:
        raise ValueError("the clamp is symmetric")
    return Sizes(
        vocab=c["vocab_size"], hidden=c["hidden_size"],
        layers=c["num_hidden_layers"],
        dense_layers=c["dense_layers_held"],
        heads=c["num_attention_heads"], q_lora=c["q_lora_rank"],
        kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
        rot=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        dense_width=c["intermediate_size"],
        experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], first_expert=c["first_expert"],
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["n_shared_experts"],
        scaling=float(c["routed_scaling_factor"]), eps=c["rms_norm_eps"],
        streams=c["hc_mult"], sinkhorn_iters=c["hc_sinkhorn_iters"],
        hc_eps=c["hc_eps"], clamp=float(c["mhc_h_res_clamp_max"]))


def sizes_record(sz: Sizes) -> dict:
    return dataclasses.asdict(sz)


def sizes_from_record(record: dict) -> Sizes:
    return Sizes(**record)


# -- YaRN ---------------------------------------------------------------------

def mscale(sz: Sizes, m: float) -> float:
    return 1.0 if sz.yarn_factor <= 1 else 0.1 * m * math.log(
        sz.yarn_factor) + 1.0


# the planted faults of the controls (drivers/train_mhc_lm.py), each a number
# 0 or 1, so that one compiled program serves all of them; None, the
# default everywhere, plants nothing and adds no operation
FAULTS = ("sinkhorn_once", "h_res_transposed", "h_post_without_its_2",
          "one_stream", "h_pre_one_over_n", "no_yarn", "no_mscale_squared",
          "stream_e4m3")


def _planted(fault, name, wrong, right):
    """`right`, or `wrong` where the fault `name` is planted."""
    if fault is None:
        return right
    return jnp.where(fault.get(name, 0) > 0, wrong, right)


def inv_freq(sz: Sizes, yarn: bool = True):
    """Equation 5's (d/2,) inverse frequencies; `yarn` False: rope_theta's
    own."""
    d, base = sz.rot, sz.rope_theta
    plain = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not yarn:
        return plain

    def correction(turns):
        return d * math.log(sz.yarn_original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(sz.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(sz.yarn_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return plain / sz.yarn_factor * (1.0 - mask) + plain * mask


def rotate_pairs(x, sz: Sizes, fault=None):
    """(N, S, heads, d) -> the same, every adjacent pair (x_2i, x_2i+1) of
    the last axis turned by pos * inv_freq_i, times equation 5's factor."""
    s, d = x.shape[1], x.shape[-1]
    freq = _planted(fault, "no_yarn", inv_freq(sz, False), inv_freq(sz))
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * freq[None, :])[None, :, None, :]
    factor = _planted(fault, "no_yarn", 1.0, mscale(sz, sz.yarn_mscale)
                      / mscale(sz, sz.yarn_mscale_all_dim))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(lp, a, sz: Sizes, q_block, dt, fault=None):
    """Equations 4-6 without the residual."""
    n, s, _ = a.shape
    c_q = rms(_mm(a, lp["w_dq"], dt), lp["g_q"], sz.eps)
    q = _mm(c_q, lp["w_uq"], dt).reshape(n, s, sz.heads, sz.nope + sz.rot)
    down = _mm(a, lp["w_dkv"], dt)
    c_kv = rms(down[..., :sz.kv_lora], lp["g_kv"], sz.eps)
    k_r = rotate_pairs(down[..., None, sz.kv_lora:], sz, fault)  # one head
    kv = _mm(c_kv, lp["w_ukv"], dt).reshape(n, s, sz.heads,
                                            sz.nope + sz.v_dim)
    q_n, q_r = q[..., :sz.nope], rotate_pairs(q[..., sz.nope:], sz, fault)
    k_n, v = kv[..., :sz.nope], kv[..., sz.nope:]
    q_n, q_r, k_n, k_r, v = (_round(x, dt) for x in (q_n, q_r, k_n, k_r, v))
    scale = _planted(fault, "no_mscale_squared", 1.0, mscale(
        sz, sz.yarn_mscale_all_dim) ** 2) / math.sqrt(sz.nope + sz.rot)
    cols = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(q_block)
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, start, q_block,
                                                     axis=1)
        scores = (jnp.einsum("nqhd,nkhd->nhqk", cut(q_n), k_n)
                  + jnp.einsum("nqhd,nkd->nhqk", cut(q_r), k_r[:, :, 0])) \
            * scale
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


# -- the hyper-connections ------------------------------------------------------

def sinkhorn(m, iters: int, eps: float, fault=None):
    """Equation 2's SK over the last two axes, (row, column): one
    iteration, then the other iters - 1 as a loop (unrolled, they made
    most of the reference's compiled code)."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    first = one(0, m)
    return _planted(fault, "sinkhorn_once", first,
                    jax.lax.fori_loop(1, iters, one, first))


def coefficients(hp, x, sz: Sizes, dt=None, fault=None):
    """Equation 2's (H_pre (N, S, n), H_post (N, S, n), H_res (N, S, n,
    n)) of a stream x (N, S, n, C)."""
    n = sz.streams
    flat = x.reshape(*x.shape[:2], -1)
    xn = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True) + sz.eps)
    proj = _mm(xn, hp["phi"], dt)                       # (N, S, 2 n + n^2)
    a, b = hp["alpha"], hp["bias"]
    h_pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    logits = a[2] * proj[..., 2 * n:] + b[2 * n:]
    m = jnp.exp(jnp.clip(logits, -sz.clamp, sz.clamp))
    return h_pre, h_post, sinkhorn(m.reshape(*m.shape[:-1], n, n),
                                   sz.sinkhorn_iters, sz.hc_eps, fault)


def hyper(hp, x, sub, sz: Sizes, dt=None, fault=None):
    """Equation 2 around the sub-layer `sub`: x (N, S, n, C) -> the same.
    `fault`: the module docstring's; `one_stream` reads stream 0 alone and
    adds sub's output to every stream unmixed, which on streams that start
    equal is the plain residual x + F(x) in each."""
    h_pre, h_post, h_res = coefficients(hp, x, sz, dt, fault)
    n = sz.streams
    if fault is not None:
        eye = jnp.eye(n, dtype=h_res.dtype)
        h_pre = _planted(fault, "h_pre_one_over_n", 1.0 / n, h_pre)
        h_post = _planted(fault, "h_post_without_its_2", 0.5, 1.0) * h_post
        h_res = _planted(fault, "h_res_transposed",
                         jnp.swapaxes(h_res, -1, -2), h_res)
        h_pre = _planted(fault, "one_stream", eye[0], h_pre)
        h_post = _planted(fault, "one_stream", 1.0, h_post)
        h_res = _planted(fault, "one_stream", eye, h_res)
    y = sub(jnp.einsum("nsi,nsic->nsc", h_pre, x))
    return jnp.einsum("nsij,nsjc->nsic", h_res, x) \
        + h_post[..., None] * y[:, :, None]


def _stream(x, fault):
    """The stream, rounded to an 8-bit float's 3 mantissa bits where that
    fault is planted: by `reduce_precision`, since XLA:TPU removes a
    float32 -> float8 -> float32 pair of casts between elementwise
    operations as a no-op (it read 2e-7 so on a v5e)."""
    return _planted(fault, "stream_e4m3", jax.lax.reduce_precision(
        x, exponent_bits=4, mantissa_bits=3), x)


def layer(lp, x, sz: Sizes, q_block=None, dt=None, fault=None):
    att = lambda h: attention(lp, rms(h, lp["g1"], sz.eps), sz, q_block, dt,
                              fault)
    ffn = lambda h: feed_forward(lp, rms(h, lp["g2"], sz.eps), sz, dt)
    x = _stream(hyper(lp["hc1"], x, att, sz, dt, fault), fault)
    return _stream(hyper(lp["hc2"], x, ffn, sz, dt, fault), fault)


@_highest
def hidden(params, tokens, sz: Sizes, q_block=None, operand_dtype=None,
           remat=False, fault=None):
    """(N, S) token ids -> (N, S, C): what the head reads, rms(sum of the
    streams; g_f). `remat`: under jax.grad keep only each layer's input
    stream and compute the layer again in the backward pass. `fault`:
    {name in FAULTS: 0 or 1}, numbers or traced, or None."""
    step = functools.partial(layer, sz=sz, q_block=q_block, dt=operand_dtype)
    step = jax.checkpoint(step) if remat else step
    e = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0)
    x = _stream(jnp.broadcast_to(e[:, :, None], (*e.shape[:2], sz.streams,
                                                 e.shape[-1])), fault)
    for lp in params["layers"]:
        x = step(lp, x, fault=fault)
    return rms(jnp.sum(x, axis=2), params["g_f"], sz.eps)


@_highest
def logits_block(params, x, lo: int, hi: int, operand_dtype=None):
    """Logits of vocabulary rows lo..hi-1 of this chip's slice."""
    return _mm(x, params["head"][:, lo:hi], operand_dtype)


def forward(params, tokens, sz: Sizes, q_block=None, operand_dtype=None):
    return logits_block(params, hidden(params, tokens, sz, q_block,
                                       operand_dtype), 0, sz.vocab,
                        operand_dtype)


def loss(params, tokens, labels, sz: Sizes, q_block=None):
    return cross_entropy(forward(params, tokens, sz, q_block), labels)


@_highest
def loss_blocked(params, tokens, labels, sz: Sizes, q_block, vocab_block,
                 operand_dtype=None, positions=None, fault=None):
    """`loss` for the chip at the timed size: the log-sum-exp from blocks of
    the vocabulary, each computed again in the backward pass, and the
    label's logit as a row-wise product; every layer computed again in the
    backward pass. `positions`: the mean over the first so many positions
    only. `fault`: `hidden`'s."""
    head = params["head"]                                  # (C, V)
    block = min(vocab_block, sz.vocab)
    n_blocks = -(-sz.vocab // block)
    padded = jnp.pad(head, ((0, 0), (0, n_blocks * block - sz.vocab)))
    x = hidden(params, tokens, sz, q_block, operand_dtype, remat=True,
               fault=fault)
    if positions is not None:
        x, labels = x[:, :positions], labels[:, :positions]
    x = x.reshape(-1, x.shape[-1])
    labels = labels.astype(jnp.int32).reshape(-1)

    @jax.checkpoint
    def lse_block(i):
        logits = _mm(x, jax.lax.dynamic_slice_in_dim(
            padded, i * block, block, axis=1), operand_dtype)
        live = i * block + jnp.arange(block) < sz.vocab
        return jax.nn.logsumexp(jnp.where(live[None, :], logits, -jnp.inf),
                                axis=-1)
    lse = jax.nn.logsumexp(jax.lax.map(lse_block, jnp.arange(n_blocks)),
                           axis=0)
    w = jnp.take(head, labels, axis=1).T                   # (tokens, C)
    return jnp.mean(lse - jnp.sum(_round(x, operand_dtype)
                                  * _round(w, operand_dtype), axis=-1))


def from_net(net_params: dict, sz: Sizes) -> dict:
    """The reference's weights out of the program's blobs (the prototxt
    `models/generate_models.py xing4_29b_a4b` emits): joyai_ref's for
    attention and the feed-forward; `HyperConnectionPre` phi (2 n + n^2, n
    C: [pre | post | res], res row-major; [out, in]), bias, alpha (a_pre,
    a_post, a_res). A linear map, so it carries gradients the same way."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)

    def hc(b):
        p = net_params[b]
        return {"phi": f32(p["phi"]).T, "bias": f32(p["bias"]),
                "alpha": f32(p["alpha"])}

    def block(b: str, dense: bool) -> dict:
        attn = net_params[f"{b}/attn"]
        out = {"hc1": hc(f"{b}/hc1"), "hc2": hc(f"{b}/hc2"),
               "g1": f32(net_params[f"{b}/ln1"]["scale"]),
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
            "head": f32(net_params["logits"]["weight"]).T}


# -- counts, from shapes alone -----------------------------------------------

def attention_weights(sz: Sizes) -> int:
    """Elements of equation 4-6's five matrices."""
    return (sz.hidden * sz.q_lora + sz.q_lora * sz.heads * (sz.nope + sz.rot)
            + sz.hidden * (sz.kv_lora + sz.rot)
            + sz.kv_lora * sz.heads * (sz.nope + sz.v_dim)
            + sz.heads * sz.v_dim * sz.hidden)


def hc_weights(sz: Sizes) -> int:
    """Elements of one sub-layer's phi, bias and alpha."""
    k = 2 * sz.streams + sz.streams ** 2
    return k * sz.streams * sz.hidden + k + 3


def param_count(sz: Sizes) -> int:
    block = attention_weights(sz) + sz.q_lora + sz.kv_lora + 2 * sz.hidden \
        + 2 * hc_weights(sz)
    unit = 3 * sz.hidden * sz.expert_width
    expert_block = (block + sz.hidden * sz.experts + sz.experts
                    + (sz.experts_held + sz.shared_experts) * unit)
    dense_block = block + 3 * sz.hidden * sz.dense_width
    return (sz.dense_layers * dense_block
            + (sz.layers - sz.dense_layers) * expert_block
            + 2 * sz.vocab * sz.hidden + sz.hidden)


def hc_macs_per_token(sz: Sizes) -> int:
    """One sub-layer's equation 2, a token: the product x' phi over n C
    lanes to 2 n + n^2 outputs, and the mixes, a multiply-add each: H_pre x
    (n C), H_res x (n^2 C), H_post^T F (n C). The norm and the Sinkhorn
    iterations are no products."""
    n, c = sz.streams, sz.hidden
    return (2 * n + n * n) * n * c + n * c + n * n * c + n * c


def macs_per_sample(sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates of one sequence of `seq` tokens: the
    projections, scores over nope + rope lanes and values over v lanes of
    the visible pairs only, the router, the shared experts, the held
    experts at their expected top_k * held / experts rows a token, the
    dense feed-forward, two hyper-connections a layer and the head. The
    embedding is a gather."""
    attention = seq * attention_weights(sz) + visible_pairs(seq) \
        * sz.heads * (sz.nope + sz.rot + sz.v_dim)
    unit = 3 * sz.hidden * sz.expert_width
    experts = seq * (sz.hidden * sz.experts + sz.shared_experts * unit) \
        + seq * sz.top_k * sz.experts_held * unit // sz.experts
    dense = seq * 3 * sz.hidden * sz.dense_width
    return (sz.layers * (attention + 2 * seq * hc_macs_per_token(sz))
            + sz.dense_layers * dense
            + (sz.layers - sz.dense_layers) * experts
            + seq * sz.hidden * sz.vocab)


def mhc_cost(sz: Sizes, batch: int, seq: int, itemsize: int = 2) -> int:
    """HBM bytes one sub-layer's equation 2 moves by its definition,
    forward and backward, each operand read and each result written once:
    forward, x read by the coefficients and by the mix (2 n C), H_pre x
    written (C), F read (C), the new stream written (n C); backward, its
    cotangent read, x read for dH_res and for the coefficients' gradient
    (2 n C), dx written, read again and written summed with the
    coefficients' part (3 n C), F read and dF written (2 C), d(H_pre x) read
    (C); the float32 coefficients (n + n^2 a token) written and read once
    each way. The stream is `itemsize` bytes an element."""
    n, c, t = sz.streams, sz.hidden, batch * seq
    stream = itemsize * t * n * c
    channels = itemsize * t * c
    coef = 4 * t * (n + n * n)
    forward = 3 * stream + 2 * channels + 2 * coef
    backward = 6 * stream + 3 * channels + 2 * coef
    return forward + backward
