"""Plain float32 reference for the benchmark's state-space configuration:
NVIDIA-Nemotron-3-Nano-30B-A3B (https://huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json, `model_type:
nemotron_h`; the family's report is Nemotron-H, arXiv:2504.03624; the mixer
is Mamba-2 / SSD, arXiv:2405.21060): forward pass, loss and, through
`jax.grad`, gradients.

This is the yardstick the `correct` check holds the system to, so it shares
nothing with the program: plain `jax.numpy`, float32, every matrix product
under `jax.default_matmul_precision("highest")`, no kernels and NO CHUNKED
ALGEBRA: the state-space recurrence is a `lax.scan` over time, one position
a step; the convolution is explicit shifted sums, attention an explicit
mask, the experts a loop over the experts held. It knows the program only
through `from_net`, which reads the program's freshly initialised arrays out
of Caffe's blob layouts (`[out, in]` for a product).

Every layer l of the pattern (`M` Mamba-2, `E` experts, `*` attention): h <-
h + mixer_l(rms(h) * g_l), rms(x) = x / sqrt(mean(x^2, -1) + eps). h_0 =
T[ids], no embedding scale. After the last layer rms(h) * g_f, the head
(untied, no bias), mean token cross-entropy from a float32 log-softmax. With
u the normed input, rows the positions t of one sequence, shift(x)_t =
x_{t-1} and zeros before the sequence; "(departure point)" marks what
config.json does not settle: the configuration file's `assumed` list says
what was chosen and why.

M  1. [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H.
   2. xBC = silu(beta + sum_i a_i * shift^(K-1-i)(xBC)): depthwise, causal,
      K taps a channel, the LAST tap on the current row.
   3. x | B | C = xBC at H P | G N | G N; head h reads group g(h) = h // (H /
      G) (departure point).
   4. delta = softplus(dt + dt_bias) (no clamp: departure point); a =
      exp(delta A), A = -exp(A_log).
   5. S_t = a_t S_{t-1} + delta_t x_t (x) B_t (P x N a head, S_0 = 0); y_t =
      S_t C_t + D x_t.
   6. y <- w_n * rms_groups(y * silu(z)): the gate FIRST, then each of the G
      groups of H P / G channels normalised on its own (departure point).
   7. out = y W_out.
E  s = sigmoid(u W_g) over all E experts; the k largest of s + b (b the
   selection bias, a constant of the step); w = scale * s_e / sum of the
   chosen s; out = sum over the chosen experts THIS CHIP HOLDS of w_e W2_e
   relu(W1_e u)^2, plus the shared expert W2_s relu(W1_s u)^2 whole. What
   the absent experts would add is left out; nothing is renormalised over
   the held ones. Under jax.grad s is a constant (the recipe trains neither
   the router nor anything through it).
*  q, k, v = u W_q, u W_k, u W_v (H_a / G_a / G_a heads of d), NO positional
   embedding (departure point), causal softmax(q k^T / sqrt(d)) v in
   float32, W_o.

For the chip, `hidden` runs the recurrence as an outer scan over blocks of
`time_block` positions around a checkpointed inner scan (so that `jax.grad`
keeps S / time_block states a layer, not S), attention in blocks of queries,
and `loss_blocked` the head in blocks of the vocabulary, with layers, query
blocks, experts and vocabulary blocks computed again in the backward pass.
`operand_dtype` rounds both operands of every matrix product to a narrower
type first; the other keywords of `hidden` (`FAULTS`) plant one fault each
for the controls that the limits are set from (PERF.md section 2).

The counting functions at the end (`macs_per_sample`, `param_count`,
`scan_cost`, `flash_cost`, `grouped_cost`) are the benchmark's own count of
what the algorithm needs, from shapes alone; mfu and the roofline shares
read them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

TILE = 128   # the MXU's width: masks are counted in tiles of this size

# the equations' sound form; a control flips one
FAULTS = {"carry_state": True, "softplus": True, "d_term": True,
          "gate_first": True, "norm_in_groups": True,
          "group_by_division": True, "causal_taps": True, "squared": True,
          "gated_expert": False, "scaling": True, "renormalised": True,
          "rotary": False,
          # the type of delta, a and the carried state, or None for float32
          "decay_dtype": None}


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    hidden: int
    pattern: str            # one letter a layer: M | E | *
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_kernel: int
    chunk: int              # the program's; the reference has no chunks
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float       # read by the `rotary` fault alone
    experts: int            # the router's width: every expert of the model
    experts_held: int       # of which this chip holds these,
    first_expert: int       # starting here
    top_k: int
    expert_width: int
    shared_width: int
    scaling: float
    eps: float

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def of_kind(self, kind: str) -> list:
        return [l for l, k in enumerate(self.pattern) if k == kind]


def sizes_from_config(config: dict, preset: dict | None = None) -> Sizes:
    """From a configuration file's keys (the published config.json's own
    names); a rehearsal preset's `sizes` overrides them."""
    c = {**config, **(preset or {}).get("sizes", {})}
    return Sizes(
        vocab=c["vocab_size"], hidden=c["hidden_size"],
        pattern=c["hybrid_override_pattern"],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
        conv_kernel=c["conv_kernel"], chunk=c["chunk_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"], first_expert=c["first_expert"],
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["moe_shared_expert_intermediate_size"]
        * c["n_shared_experts"],
        scaling=float(c["routed_scaling_factor"]),
        eps=c["layer_norm_epsilon"])


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


def _round(x, dt):
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _low(x, dt):
    """x rounded to `dt`'s exponent and mantissa, still float32, by
    `lax.reduce_precision`: XLA:TPU removes a float32 -> bf16 -> float32
    pair of casts between elementwise operations (excess precision is
    allowed by default), and the planted fault would be no fault (read on
    the chip, PR 40: logits distance 0.00000 with the casts)."""
    if dt is None:
        return x
    info = jnp.finfo(dt)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _mm(a, b, operand_dtype=None):
    return jnp.matmul(_round(a, operand_dtype), _round(b, operand_dtype))


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def shift(x, by: int):
    """shift(x)_t = x_{t-by} along axis 1, zeros outside the sequence; a
    negative `by` reads later rows."""
    if not by:
        return x
    pad = jnp.zeros_like(x[:, :abs(by)])
    return jnp.concatenate([pad, x[:, :-by]] if by > 0 else [x[:, -by:], pad],
                           axis=1)


def recurrence(x, a, delta, b, c, time_block=None, by_division=True,
               state_dtype=None):
    """Equation 5 without its D term, one position a step: x (N, S, H, P),
    a and delta (N, S, H), b and c (N, S, G, N_state) -> y (N, S, H, P)."""
    n, s, h, p = x.shape
    g = b.shape[2]
    # head h reads group h // (H / G); the planted fault reads h % G
    of_heads = (lambda t: jnp.repeat(t, h // g, axis=1)) if by_division \
        else (lambda t: jnp.tile(t, (1, h // g, 1)))

    def step(state, at):
        x_t, a_t, d_t, b_t, c_t = at
        state = a_t[..., None, None] * state \
            + (d_t[..., None] * x_t)[..., None] * of_heads(b_t)[:, :, None]
        state = _low(state, state_dtype)
        return state, jnp.sum(state * of_heads(c_t)[:, :, None], axis=-1)

    block = min(time_block or s, s)
    if s % block:
        raise ValueError(f"time block {block} does not divide {s}")
    rows = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // block, block, n, *t.shape[2:])
    # under jax.grad: the state at each block's start is kept, the block's
    # own states computed again
    _, y = jax.lax.scan(
        jax.checkpoint(lambda state, ats: jax.lax.scan(step, state, ats)),
        jnp.zeros((n, h, p, b.shape[-1]), jnp.float32),
        tuple(rows(t) for t in (x, a, delta, b, c)))
    return jnp.moveaxis(y.reshape(s, n, h, p), 0, 1)


# the terms of FAULTS that `scan` reads
SCAN_TERMS = ("softplus", "decay_dtype", "carry_state", "group_by_division")


def scan(x, raw, dt_bias, a_log, b, c, chunk: int, time_block=None, **how):
    """Equations M 4-5 without the D term: x (N, S, H, P), raw (N, S, H)
    the time step before its bias and softplus, dt_bias and a_log (H,), b
    and c (N, S, G, N_state) -> y (N, S, H, P). Of `how` it reads
    SCAN_TERMS (`carry_state`: dropped at the edges of chunks of
    `chunk`)."""
    how = {**FAULTS, **how}
    low = how["decay_dtype"]
    delta = raw + dt_bias
    delta = _low(jax.nn.softplus(delta) if how["softplus"] else delta, low)
    a = _low(jnp.exp(_low(delta * -jnp.exp(a_log), low)), low)
    if not how["carry_state"]:
        # the state dropped at the edges of the program's chunks
        a = jnp.where((jnp.arange(x.shape[1]) % chunk == 0)[None, :, None],
                      0.0, a)
    return recurrence(x, a, delta, b, c, time_block,
                      how["group_by_division"], low)


def probe_inputs(key, seq: int, sz: Sizes, time_step, dtype):
    """Inputs of the scan alone on which the decays decide the result: one
    sequence of unit-normal x, B and C in `dtype`, A_log and dt_bias as the
    layer starts them (ln(h + 1); softplus(dt_bias) log-uniform between
    `time_step`'s first two, floored at its third), and a raw time step of
    nought at every position. A head's decay is then the same number at
    every step, so a decay that is rounded is a drift of the state and not
    a walk around it; and no D term stands beside what came through the
    state. -> x, raw, dt_bias, a_log, b, c."""
    low, high, floor = time_step
    kx, kb, kc, kt = jax.random.split(key, 4)
    h, g = sz.ssm_heads, sz.ssm_groups
    normal = lambda k, *shape: jax.random.normal(k, shape).astype(dtype)
    t = jnp.maximum(jnp.exp(jax.random.uniform(
        kt, (h,), jnp.float32, math.log(low), math.log(high))), floor)
    return (normal(kx, 1, seq, h, sz.ssm_head_dim),
            jnp.zeros((1, seq, h), dtype), t + jnp.log(-jnp.expm1(-t)),
            jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
            normal(kb, 1, seq, g, sz.ssm_state),
            normal(kc, 1, seq, g, sz.ssm_state))


def mamba(lp, u, sz: Sizes, time_block=None, dt=None, **how):
    """Equations M 1-7."""
    how = {**FAULTS, **how}
    n, s, _ = u.shape
    h, p, g, st = sz.ssm_heads, sz.ssm_head_dim, sz.ssm_groups, sz.ssm_state
    inner, k = h * p, sz.conv_kernel
    z, xbc, raw = jnp.split(_mm(u, lp["w_in"], dt),
                            [inner, 2 * inner + 2 * g * st], axis=-1)
    # the planted fault moves every tap one row later: the last tap reads
    # the NEXT row
    late = 0 if how["causal_taps"] else 1
    xbc = jax.nn.silu(lp["conv_bias"] + sum(
        lp["conv"][:, i] * shift(xbc, k - 1 - i - late) for i in range(k)))
    x, b, c = jnp.split(xbc, [inner, inner + g * st], axis=-1)
    x = x.reshape(n, s, h, p)
    y = scan(x, raw, lp["dt_bias"], lp["a_log"], b.reshape(n, s, g, st),
             c.reshape(n, s, g, st), sz.chunk, time_block, **how)
    if how["d_term"]:
        y = y + lp["d"][:, None] * x
    y, gate = y.reshape(n, s, inner), jax.nn.silu(z)
    groups = g if how["norm_in_groups"] else 1
    norm = lambda t: rms(t.reshape(n, s, groups, -1), 1.0,
                         sz.eps).reshape(n, s, inner) * lp["w_n"]
    y = norm(y * gate) if how["gate_first"] else norm(y) * gate
    return _mm(y, lp["w_out"], dt)


def route(scores, select_bias, sz: Sizes, scaling=True, renormalised=True):
    """The choice of layer E: (indices (.., k), weights (.., k))."""
    _, idx = jax.lax.top_k(scores + select_bias, sz.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalised:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * (sz.scaling if scaling else 1.0)


def unit(u, up, down, dt, how):
    """W2 relu(W1 u)^2; the planted faults: relu alone, and a gated unit
    whose gate product is the same product once more."""
    a = _mm(u, up, dt)
    f = jax.nn.relu(a)
    if how["squared"]:
        f = f * f
    if how["gated_expert"]:
        f = f * a
    return _mm(f, down, dt)


def experts(lp, u, sz: Sizes, dt=None, first_expert=None, held=None,
            shared=True, **how):
    """Layer E: the part of the result that experts first_expert ..
    first_expert + held - 1 give, plus (with `shared`) the shared expert's.
    `lp["up"|"down"]` hold those experts' matrices."""
    how = {**FAULTS, **how}
    first = sz.first_expert if first_expert is None else first_expert
    held = sz.experts_held if held is None else held
    scores = jax.nn.sigmoid(_mm(u, lp["router"], dt))
    # routing is a constant of the training step, as in the recipe (the
    # configuration's `assumed`)
    idx, w = jax.lax.stop_gradient(route(
        scores, lp["select_bias"], sz, how["scaling"], how["renormalised"]))

    @jax.checkpoint   # under jax.grad: one expert's intermediates at a time
    def add(y, expert):
        e, up, down = expert
        chosen = idx == first + e                          # (.., k)
        w_e = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1)  # 0 if not chosen
        return y + jnp.where(jnp.any(chosen, -1)[..., None],
                             w_e[..., None] * unit(u, up, down, dt, how),
                             0.0), None
    # a loop over the held experts, one after the other, each over every
    # row and masked to the rows that chose it
    out = jax.lax.scan(add, jnp.zeros_like(u),
                       (jnp.arange(held), lp["up"][:held],
                        lp["down"][:held]))[0]
    if shared:
        out = out + unit(u, lp["s_up"], lp["s_down"], dt, how)
    return out


def rotate(x, theta: float):
    """(N, S, heads, d) turned to positions 0..S-1, rotate-half: only the
    `rotary` fault calls it."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(lp, u, sz: Sizes, q_block=None, dt=None, **how):
    """Layer *: causal, scale 1 / sqrt(d), float32 softmax, in blocks of
    `q_block` queries, no positions."""
    how = {**FAULTS, **how}
    n, s, _ = u.shape
    d, group = sz.head_dim, sz.heads // sz.kv_heads
    q = _mm(u, lp["wq"], dt).reshape(n, s, sz.heads, d)
    k = _mm(u, lp["wk"], dt).reshape(n, s, sz.kv_heads, d)
    v = _mm(u, lp["wv"], dt).reshape(n, s, sz.kv_heads, d)
    if how["rotary"]:
        q, k = rotate(q, sz.rope_theta), rotate(k, sz.rope_theta)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    q, k, v = (_round(x, dt) for x in (q, k, v))
    cols = jnp.arange(s)

    def block(start):
        rows = start + jnp.arange(q_block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        scores = jnp.einsum("nqhd,nkhd->nhqk", qb, k) / math.sqrt(d)
        seen = cols[None, :] <= rows[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        p = _round(jax.nn.softmax(scores, axis=-1), dt)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    q_block = min(q_block or s, s)
    if s % q_block:
        raise ValueError(f"query block {q_block} does not divide {s}")
    # under jax.grad a block's scores are computed again, not kept
    o = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(n, s, sz.heads * d), lp["wo"],
               dt)


def layer(kind: str, lp, h, sz: Sizes, q_block=None, time_block=None,
          dt=None, **how):
    u = rms(h, lp["g"], sz.eps)
    if kind == "M":
        return h + mamba(lp, u, sz, time_block, dt, **how)
    if kind == "E":
        return h + experts(lp, u, sz, dt, **how)
    if kind == "*":
        return h + attention(lp, u, sz, q_block, dt, **how)
    raise ValueError(f"layer kind {kind!r}: M, E or *")


@_highest
def hidden(params, ids, sz: Sizes, q_block=None, operand_dtype=None,
           remat=False, time_block=None, **how):
    """(N, S) token ids -> (N, S, D), the last norm applied. `remat`: under
    jax.grad keep only each layer's input and compute the layer again in
    the backward pass (the timed size on the chip). `how`: a planted fault
    (`FAULTS`)."""
    h = jnp.take(params["table"], ids.astype(jnp.int32), axis=0)
    for kind, lp in zip(sz.pattern, params["layers"]):
        step = functools.partial(layer, kind, sz=sz, q_block=q_block,
                                 time_block=time_block, dt=operand_dtype,
                                 **how)
        h = (jax.checkpoint(step) if remat else step)(lp, h)
    return rms(h, params["g_f"], sz.eps)


@_highest
def logits_block(params, x, lo: int, hi: int, operand_dtype=None):
    """Logits of vocabulary rows lo..hi-1 of this chip's slice."""
    return _mm(x, params["head"][:, lo:hi], operand_dtype)


def forward(params, ids, sz: Sizes, q_block=None, operand_dtype=None, **how):
    x = hidden(params, ids, sz, q_block, operand_dtype, **how)
    return logits_block(params, x, 0, sz.vocab, operand_dtype)


def loss(params, ids, labels, sz: Sizes, q_block=None, **how):
    """Mean token cross-entropy over the slice, float32 log-softmax."""
    logp = jax.nn.log_softmax(forward(params, ids, sz, q_block, **how), -1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)


loss_and_grads = jax.value_and_grad(loss)


@_highest
def loss_blocked(params, ids, labels, sz: Sizes, q_block, vocab_block,
                 operand_dtype=None, time_block=None, **how):
    """`loss` for the chip at the timed size, where the (tokens, vocabulary)
    logits may not be held whole, least of all under jax.grad: the
    log-sum-exp from blocks of the vocabulary, each computed again in the
    backward pass, and the label's logit as a row-wise product. The same
    number as `loss`."""
    x = hidden(params, ids, sz, q_block, operand_dtype, remat=True,
               time_block=time_block, **how)
    x = x.reshape(-1, x.shape[-1])
    labels = labels.astype(jnp.int32).reshape(-1)
    head = params["head"]                                  # (D, V)
    block_cols = min(vocab_block, sz.vocab)
    n_blocks = -(-sz.vocab // block_cols)
    padded = jnp.pad(head, ((0, 0), (0, n_blocks * block_cols - sz.vocab)))

    @jax.checkpoint
    def lse_block(i):
        logits = _mm(x, jax.lax.dynamic_slice_in_dim(
            padded, i * block_cols, block_cols, axis=1), operand_dtype)
        live = i * block_cols + jnp.arange(block_cols) < sz.vocab
        return jax.nn.logsumexp(jnp.where(live[None, :], logits, -jnp.inf),
                                axis=-1)
    lse = jax.nn.logsumexp(jax.lax.map(lse_block, jnp.arange(n_blocks)),
                           axis=0)
    w = jnp.take(head, labels, axis=1).T                   # (tokens, D)
    return jnp.mean(lse - jnp.sum(_round(x, operand_dtype)
                                  * _round(w, operand_dtype), axis=-1))


def from_net(net_params: dict, sz: Sizes) -> dict:
    """The reference's weights out of the program's blobs (the prototxt
    `models/generate_models.py nemotron_h` emits). `Embed.weight` (V, D);
    the head `InnerProduct.weight` (V, D), [out, in]; `RMSNorm.scale`.
    `Mamba2`: `in_weight` (2 H P + 2 G N + H, D) and `out_weight` (D, H P),
    [out, in]; `conv_weight` (H P + 2 G N, K), the LAST tap the current
    position; `conv_bias`, `dt_bias`, `A_log`, `D`, `norm_scale`.
    `Attention.qkv_weight` ((H_a + 2 G_a) d, D): q rows, then k, then v;
    `proj_weight` (D, H_a d). `MoE`: `gate` (D, E), `select_bias` (E,), `w1`
    (held, D, F), `w2` (held, F, D), `shared_w1` (D, Fs), `shared_w2` (Fs,
    D). A linear map, so it carries gradients the same way."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    layers = []
    for l, kind in enumerate(sz.pattern):
        at = lambda name: net_params[f"blk{l}/{name}"]
        lp = {"g": f32(at("norm")["scale"])}
        if kind == "M":
            m = at("ssm")
            lp.update(
                w_in=f32(m["in_weight"]).T, conv=f32(m["conv_weight"]),
                conv_bias=f32(m["conv_bias"]), dt_bias=f32(m["dt_bias"]),
                a_log=f32(m["A_log"]), d=f32(m["D"]),
                w_n=f32(m["norm_scale"]), w_out=f32(m["out_weight"]).T)
        elif kind == "E":
            m = at("moe")
            lp.update(
                router=f32(m["gate"]), select_bias=f32(m["select_bias"]),
                up=f32(m["w1"]), down=f32(m["w2"]),
                s_up=f32(m["shared_w1"]), s_down=f32(m["shared_w2"]))
        else:
            m = at("attn")
            qkv = f32(m["qkv_weight"])
            lp.update(wq=qkv[:nq].T, wk=qkv[nq:nq + nkv].T,
                      wv=qkv[nq + nkv:].T, wo=f32(m["proj_weight"]).T)
        layers.append(lp)
    return {"table": f32(net_params["embed"]["weight"]), "layers": layers,
            "g_f": f32(net_params["ln_f"]["scale"]),
            "head": f32(net_params["logits"]["weight"]).T}


# -- counts, from shapes alone -----------------------------------------------

def layer_params(kind: str, sz: Sizes) -> int:
    """Learnable parameters of one layer with its pre-norm, the held
    experts alone of an expert layer's."""
    d = sz.hidden
    if kind == "M":
        inner = sz.ssm_inner
        conv = inner + 2 * sz.ssm_groups * sz.ssm_state
        return (d * (inner + conv + sz.ssm_heads) + inner * d
                + conv * (sz.conv_kernel + 1) + 3 * sz.ssm_heads + inner + d)
    if kind == "E":
        return (d * sz.experts + sz.experts
                + sz.experts_held * 2 * d * sz.expert_width
                + 2 * d * sz.shared_width + d)
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return d * (nq + 2 * nkv) + nq * d + d


def param_count(sz: Sizes) -> int:
    return (sum(layer_params(kind, sz) for kind in sz.pattern)
            + 2 * sz.vocab * sz.hidden + sz.hidden)


def visible_pairs(seq: int) -> int:
    """(query, key) pairs of one head the causal mask leaves."""
    return seq * (seq + 1) // 2


def visible_tiles(seq: int, tile: int = TILE) -> int:
    """Tiles of `tile` x `tile` (query, key) pairs that hold at least one
    visible pair: what a tiled kernel cannot avoid visiting."""
    n = -(-seq // tile)
    return n * (n + 1) // 2


def scan_macs_per_token(sz: Sizes) -> int:
    """Multiply-accumulates a position of equation 5 by its definition (what
    `macs_per_sample` and `scan_cost` count): the state update reads x (x) B
    into H P N state elements, the read-out each of them against C. A
    chunked form multiplies more, growing with its chunk (ops/ssd.py at
    chunks of 128: 1.70 M a position for these 1.05 M), and is credited no
    more."""
    return 2 * sz.ssm_inner * sz.ssm_state


def layer_macs_per_token(kind: str, sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates a token of one layer, attention's
    scores and values apart (they are counted over the visible pairs)."""
    d = sz.hidden
    if kind == "M":
        inner = sz.ssm_inner
        wide = 2 * inner + 2 * sz.ssm_groups * sz.ssm_state + sz.ssm_heads
        return d * wide + inner * d + scan_macs_per_token(sz)
    if kind == "E":
        return (d * sz.experts + 2 * d * sz.shared_width
                + sz.top_k * sz.experts_held * 2 * d * sz.expert_width
                // sz.experts)
    nq, nkv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    return d * (nq + 2 * nkv) + nq * d


def macs_per_sample(sz: Sizes, seq: int) -> int:
    """Forward multiply-accumulates of one sequence of `seq` tokens: every
    product of the three kinds of layer (the Mamba-2 recurrence by its
    definition, the convolution a channel is no product; the held experts at
    their expected top_k * held / experts rows a token, the shared expert
    for every token; scores and values over the visible pairs only), and
    the head. The embedding is a gather."""
    per_token = sum(layer_macs_per_token(kind, sz, seq)
                    for kind in sz.pattern)
    attention = len(sz.of_kind("*")) * 2 * visible_pairs(seq) \
        * sz.heads * sz.head_dim
    return seq * (per_token + sz.hidden * sz.vocab) + attention


def scan_cost(sz: Sizes, batch: int, seq: int, backward: bool = True,
              itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) equation 5 needs in ONE layer, by its definition and
    not by any one way of computing it: a position's state update and its
    read-out are 2 H P N multiply-accumulates, which no chunked form
    undercuts (one reads each position's x (x) B into the state, the other
    each state element against C), and x, B, C, delta and y pass through
    memory once. With `backward` the transposed recurrence costs the same
    two again, each input is read once more and its cotangent written, and
    y's cotangent read."""
    macs = scan_macs_per_token(sz)
    moved = 2 * sz.ssm_inner + 2 * sz.ssm_groups * sz.ssm_state \
        + sz.ssm_heads
    passes = 3 if backward else 1
    return (2 * macs * batch * seq * passes,
            itemsize * moved * batch * seq * passes)


# FLOPs a (query, key) pair of one head costs each flash kernel, in units
# of the head size d: forward QK^T and PV; dQ recomputes QK^T, then dO V^T
# and dS K; dK/dV recomputes QK^T, then P^T dO, dO V^T and dS^T Q
FLASH_FLOPS_PER_PAIR = {"flash_fwd": 4, "flash_dq": 6, "flash_dkv": 8}


def flash_cost(kernel: str, sz: Sizes, batch: int, seq: int,
               itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one call of a flash kernel needs: the matrix products
    over the 128 x 128 tiles the causal mask leaves, H_a query heads, and
    each operand read and each result written once (q, o, dO and dQ over
    the query heads, k, v, dK and dV over the key/value heads, the float32
    row statistics)."""
    pairs = visible_tiles(seq) * TILE * TILE
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
    """(FLOPs, bytes) the two grouped products of an ungated expert layer
    need for `rows` tokens routed to held experts, forward: 2 FLOPs a
    multiply-accumulate; the rows read, the intermediate written and read,
    the result written, and every held expert's matrices read once. The
    backward pass costs twice this."""
    flops = 2 * rows * 2 * sz.hidden * sz.expert_width
    nbytes = itemsize * (2 * rows * sz.hidden + 2 * rows * sz.expert_width
                         + sz.experts_held * 2 * sz.hidden * sz.expert_width)
    return flops, nbytes
