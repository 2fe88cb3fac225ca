# lint: ok(reference-citation) — TPU-native op: the CNN-era reference has
# no state-space layer (SURVEY §5.7); there is no analogue to cite
"""The selective state-space recurrence of Mamba-2 (SSD, arXiv:2405.21060)
in its chunked form, in plain `jnp`: no kernel.

Per head h of P lanes, with a state S (P, N), group g(h) = h // (H / G):

    delta_t = softplus(dt_t + dt_bias)            a_t = exp(delta_t A)
    S_t = a_t S_{t-1} + delta_t x_t (x) B_t       A = -exp(A_log) < 0
    y_t = S_t C_t + D x_t                         S_0 = 0

A recurrence over S positions is S sequential steps of an outer product:
nothing an MXU can do. Over chunks of Q positions the same numbers are four
products and a short sum (with l = the running sum of delta A inside a
chunk, inclusive, float32):

    inside a chunk   y_i += sum_{j <= i} (C_i . B_j) exp(l_i - l_j) delta_j x_j
                     (a Q x Q masked matrix a head, times x)
    a chunk's end    Z_c = sum_j exp(l_Q - l_j) delta_j x_j (x) B_j
    across chunks    S_in[c] = sum_{c' < c} exp(L_{c-1} - L_{c'}) Z_{c'}
                     (L the running sum of the chunks' whole decays: the
                     S / Q chunk states carried in closed form, one small
                     float32 product a head at HIGHEST precision, no loop)
    read-out         y_i += exp(l_i) C_i . S_in[c]

Every `exp`, every running sum and the carried state are float32 (a decay
is a product of up to S factors a little under one: in bf16 the running
sum loses the factors themselves); the four products take operands in x's
type and accumulate in float32. Every exponent is a difference of a running
sum of negatives taken later-minus-earlier, so it is at most nought and no
`exp` can overflow; what the mask leaves out is set to -inf BEFORE the
`exp`, not multiplied away after it.

The backward pass is jax's own of this function under a `jax.checkpoint`
that keeps the carried states `S_in` alone (`KEPT`): the Q x Q matrices (S
Q H float32 values a layer, 268 MB at 8,192 x 128 x 64) are computed again,
not kept. Under a `Mamba2` layer's `remat` the layer's checkpoint keeps
`KEPT` and this function's result as well (`Mamba2Layer.kept_under_remat`),
so the backward pass runs the forward once, inside this checkpoint.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

KEPT = "ssd_carried_states"


def _ssd(x, dt, a_log, b, c, d, dt_bias, chunk: int):
    n, s, h, p = x.shape
    g, st = b.shape[2:]
    q = min(chunk, s)
    nc, r = s // q, h // g
    f32 = jnp.float32
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)

    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))  # (n,s,h)
    decay = delta * -jnp.exp(a_log.astype(f32))                    # log a_t
    # heads as (group, head of the group): B and C are a group's
    delta = delta.reshape(n, nc, q, g, r)
    xc = x.reshape(n, nc, q, g, r, p)
    bc, cc = b.reshape(n, nc, q, g, st), c.reshape(n, nc, q, g, st)
    # a running sum is a product with the 0/1 lower triangle, float32 at
    # HIGHEST precision: XLA:TPU's own `cumsum` over 128 of 2 MB took 1.9
    # ms, more than everything else of a forward pass here together
    # (PERF.md section 6, PR 40)
    seen = jnp.tril(jnp.ones((q, q), bool))
    running = lambda t, mask: jnp.einsum(
        "...j,ij->...i", t, mask.astype(f32),
        precision=jax.lax.Precision.HIGHEST)
    lt = running(jnp.moveaxis(decay.reshape(n, nc, q, g, r), 2, -1),
                 seen)                                             # (n,c,g,r,q)
    l = jnp.moveaxis(lt, -1, 2)                                    # (n,c,q,g,r)

    # inside a chunk: (C_i . B_j) exp(l_i - l_j) delta_j over j <= i
    within = jnp.exp(jnp.where(seen, lt[..., :, None] - lt[..., None, :],
                               -jnp.inf))                          # (..,i,j)
    cb = dot("ncigs,ncjgs->ncgij", cc, bc)
    m = cb[:, :, :, None] * within \
        * jnp.moveaxis(delta, 2, -1)[..., None, :]
    y = dot("ncgrij,ncjgrp->ncigrp", m.astype(x.dtype), xc)

    # each chunk's end state, the carried states, their read-out
    to_end = jnp.exp(l[:, :, -1:] - l) * delta                     # (n,c,q,g,r)
    z = dot("ncjgrp,ncjgs->ncgrps",
            (xc * to_end[..., None]).astype(x.dtype), bc)
    # L_c, chunks last: the running sum of the chunks' whole decays, and
    # L_{c-1} by a shift (not L_c less the chunk's own decay: a chunk's
    # later positions must not reach its carried state, not by a rounding)
    whole = running(jnp.moveaxis(lt[..., -1], 1, -1),
                    jnp.tril(jnp.ones((nc, nc), bool)))            # (n,g,r,c)
    upto = jnp.pad(whole, [(0, 0)] * 3 + [(1, 0)])[..., :-1]
    before = jnp.tril(jnp.ones((nc, nc), bool), -1)
    # L_{c-1} - L_{c'}: the decays of the chunks strictly between
    carry = jnp.exp(jnp.where(
        before, upto[..., :, None] - whole[..., None, :], -jnp.inf))
    s_in = checkpoint_name(
        jnp.einsum("ngrcd,ndgrps->ncgrps", carry, z,
                   precision=jax.lax.Precision.HIGHEST), KEPT)
    y = y + dot("ncigs,ncgrps->ncigrp", cc, s_in.astype(x.dtype)) \
        * jnp.exp(l)[..., None]
    y = y.reshape(n, s, h, p) + d.astype(f32)[:, None] * x
    return y.astype(x.dtype)


def ssd(x, dt, a_log, b, c, d, dt_bias, chunk: int):
    """x (N, S, H, P), dt (N, S, H) before its bias and softplus, a_log, d,
    dt_bias (H,), b, c (N, S, G, state) -> y (N, S, H, P) in x's type. S is
    whole chunks of `chunk`, or one shorter chunk, and H whole groups
    (proto/netshape.py `mamba2_problem` refuses the rest for the layer)."""
    return jax.checkpoint(
        functools.partial(_ssd, chunk=chunk),
        policy=jax.checkpoint_policies.save_only_these_names(KEPT))(
            x, dt, a_log, b, c, d, dt_bias)
