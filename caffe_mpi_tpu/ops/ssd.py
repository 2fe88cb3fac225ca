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

The backward pass is written out from the same equations (`_ssd_bwd`, a
custom VJP rule) and runs no forward pass again. It reads the inputs and what
the forward rule keeps under `KEPT`: the carried states `S_in` (S / Q x H
P N float32 values, 134 MB a layer at 8,192 x 128 x 64), the running sums
l (S x H) and the carry between chunks (H (S / Q)^2). The Q x Q matrices
(S Q H float32 values, 268 MB a layer) are neither kept nor written: M is
built again from l, C B^T and delta where it is read, in the one pass over
dM = dy x^T that gathers M's row and column sums, and as the operand of dx
+= M^T dy. Across chunks it is the closed-form carry transposed, dZ =
carry^T dS_in (float32, HIGHEST), and the chunks' decays take their
gradient from the recurrence form S_in[c+1] = exp(L_c) S_in[c] + Z_c, as
exp(L_c) <dZ_c, S_in[c]>, so Z is neither kept nor built again. The
running sums go back as the transposed triangle's product. The backward
pass keeps the forward's precision: every `exp`, running sum, decay,
carried state and their cotangents float32, the products' operands in x's
type with float32 accumulation; dM and d(x to_end) are written in x's
type, as jax's own backward pass of the forward writes them, after their
float32 sums are taken. Under a `Mamba2` layer's `remat` the layer keeps
`KEPT` and this function's result (`Mamba2Layer.kept_under_remat`), so its
backward pass does not enter the forward rule. The backward rule opens
the `ssm.scan` scope itself; `ssd` and the backward rule are jitted, so
that one trace of each serves every layer of a recipe at the same
shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..utils.spans import SSM_SCAN

KEPT = "ssd_carried_states"
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _running(t, mask):
    """out_i = sum_j mask_ij t_j over the last axis: a running sum is a
    product with the 0/1 lower triangle, float32 at HIGHEST precision.
    XLA:TPU's own `cumsum` over 128 of 2 MB took 1.9 ms, more than
    everything else of a forward pass here together (PERF.md section
    6)."""
    return jnp.einsum("...j,ij->...i", t, mask.astype(_F32),
                      precision=_HIGHEST)


# Both passes hold a head's positions on the last axis, as the products
# write them: (n, s, h, p) as (n, c, g, r, p, q) and (n, s, h) as (n, c,
# g, r, q), a head h = (g, r) of r = H / G a group, a position s = (c, q);
# B and C as (n, c, g, N, q).
def _heads(v, q: int, g: int):
    """(n, S, H, P) -> (n, c, g, r, P, Q), written as `_unheads` is: the
    layer's view keeps the positions last, and whole (8, 128) tiles of
    (lanes, positions) move."""
    n, s, h, p = v.shape
    lo = math.gcd(p, 8)
    v = jnp.moveaxis(v, 1, -1).reshape(n, g, h // g, p // lo, lo, s // q, q)
    return v.transpose(0, 5, 1, 2, 3, 4, 6).reshape(n, s // q, g, h // g, p, q)


def _unheads(v):
    """The inverse of `_heads`, written so that XLA:TPU moves whole (8,
    128) tiles of (lanes, positions) once: a head's lanes split in eights,
    the chunks moved between the two parts, and the rest a reshape (one
    copy of x's size where the plain transpose took two)."""
    n, nc, g, r, p, q = v.shape
    lo = math.gcd(p, 8)
    v = v.reshape(n, nc, g, r, p // lo, lo, q).transpose(0, 2, 3, 4, 5, 1, 6)
    return jnp.moveaxis(v.reshape(n, g * r, p, nc * q), -1, 1)


def _lane(v, q: int, g: int):
    n, s, h = v.shape
    return jnp.moveaxis(v.reshape(n, s // q, q, g, h // g), 2, -1)


def _unlane(v):
    n, nc, g, r, q = v.shape
    return jnp.moveaxis(v, -1, 2).reshape(n, nc * q, g * r)


def _forward(x, dt, a_log, b, c, d, dt_bias, chunk: int):
    """y, and what the backward pass reads of the forward: the carried
    states S_in (n, c, g, r, P, N), the running sums l (n, c, g, r, Q) and
    the carry between chunks (n, g, r, c, c), float32, named `KEPT`."""
    s, h, g = x.shape[1], x.shape[2], b.shape[2]
    q = min(chunk, s)
    nc, r = s // q, h // g
    dot = functools.partial(jnp.einsum, preferred_element_type=_F32)

    delta = _lane(jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32)),
                  q, g)                                     # (n,c,g,r,q)
    decay = delta * -jnp.exp(a_log.astype(_F32)).reshape(g, r, 1)  # log a_t
    xh = _heads(x, q, g)                                    # (n,c,g,r,p,q)
    bh, ch = (_heads(v, q, g)[:, :, :, 0] for v in (b, c))  # (n,c,g,N,q)
    seen = jnp.tril(jnp.ones((q, q), bool))
    lt = checkpoint_name(_running(decay, seen), KEPT)       # l

    # inside a chunk: (C_i . B_j) exp(l_i - l_j) delta_j over j <= i
    within = jnp.exp(jnp.where(seen, lt[..., :, None] - lt[..., None, :],
                               -jnp.inf))                   # (..,i,j)
    cb = dot("ncgsi,ncgsj->ncgij", ch, bh)
    m = cb[:, :, :, None] * within * delta[..., None, :]
    y = dot("ncgrpj,ncgrij->ncgrpi", xh, m.astype(x.dtype))

    # each chunk's end state, the carried states, their read-out
    to_end = jnp.exp(lt[..., -1:] - lt) * delta
    z = dot("ncgrpj,ncgsj->ncgrps",
            (xh * to_end[..., None, :]).astype(x.dtype), bh)
    # L_c, chunks last: the running sum of the chunks' whole decays, and
    # L_{c-1} by a shift (not L_c less the chunk's own decay: a chunk's
    # later positions must not reach its carried state, not by a rounding)
    whole = _running(jnp.moveaxis(lt[..., -1], 1, -1),
                     jnp.tril(jnp.ones((nc, nc), bool)))           # (n,g,r,c)
    upto = jnp.pad(whole, [(0, 0)] * 3 + [(1, 0)])[..., :-1]
    before = jnp.tril(jnp.ones((nc, nc), bool), -1)
    # L_{c-1} - L_{c'}: the decays of the chunks strictly between
    carry = checkpoint_name(jnp.exp(jnp.where(
        before, upto[..., :, None] - whole[..., None, :], -jnp.inf)), KEPT)
    s_in = checkpoint_name(
        jnp.einsum("ngrcd,ndgrps->ncgrps", carry, z, precision=_HIGHEST),
        KEPT)
    y = y + dot("ncgrps,ncgsi->ncgrpi", s_in.astype(x.dtype), ch) \
        * jnp.exp(lt)[..., None, :]
    # written in x's type before its positions move back beside the heads
    y = y + d.astype(_F32).reshape(g, r, 1, 1) * xh
    return _unheads(y.astype(x.dtype)), (s_in, lt, carry)


def _ssd(x, dt, a_log, b, c, d, dt_bias, chunk: int):
    """The chunked form's forward pass; jax's autodiff of it is the
    backward rule's yardstick in the tests."""
    return _forward(x, dt, a_log, b, c, d, dt_bias, chunk)[0]


def _ssd_fwd(x, dt, a_log, b, c, d, dt_bias, chunk):
    y, kept = _forward(x, dt, a_log, b, c, d, dt_bias, chunk)
    return y, (x, dt, a_log, b, c, d, dt_bias, kept)


def _ssd_bwd(chunk, res, dy):
    """The seven cotangents from the chunked form's equations (the module's
    notation, W_ij = exp(l_i - l_j) for j <= i, M_ij = (C_i . B_j) W_ij
    delta_j). A head's positions are the last axis throughout, (n, c, g,
    r, P, Q) and (n, c, g, r, Q), as the products write them. dl gathers
    the gradient of every running sum l_i: from the read-out, the chunks'
    end states, the chunks' decays and M's rows and columns; it goes back
    to delta and A through the transposed triangle."""
    x, dt, a_log, b, c, d, dt_bias, (s_in, lt, carry) = res
    with jax.named_scope(SSM_SCAN):
        s, h, g = x.shape[1], x.shape[2], b.shape[2]
        q, r = min(chunk, s), h // g
        t = x.dtype
        dot = functools.partial(jnp.einsum, preferred_element_type=_F32)
        # (n, c, g, N, Q) -> (n, S, g, N)
        ungroup = lambda v: _unheads(v[:, :, :, None])

        u = _lane(dt.astype(_F32) + dt_bias.astype(_F32), q, g)
        delta = jax.nn.softplus(u)                          # (n,c,g,r,q)
        a = -jnp.exp(a_log.astype(_F32)).reshape(g, r, 1)
        xh, dyh = _heads(x, q, g), _heads(dy, q, g)         # (n,c,g,r,p,q)
        dyf = dyh.astype(_F32)
        bh, ch = (_heads(v, q, g)[:, :, :, 0] for v in (b, c))  # (n,c,g,N,q)
        el = jnp.exp(lt)

        # read-out y_i += exp(l_i) C_i . S_in[c]
        s_t = s_in.astype(t)
        e = (dyf * el[..., None, :]).astype(t)
        ds_in = dot("ncgrpi,ncgsi->ncgrps", e, ch)
        dc = dot("ncgrps,ncgrpi->ncgsi", s_t, e)
        dl = el * jnp.sum(dyf * dot("ncgrps,ncgsi->ncgrpi", s_t, ch), -2)

        # across chunks: S_in = carry Z, so dZ = carry^T dS_in; L_c, the
        # chunk's whole decay, by S_in[c+1] = exp(L_c) S_in[c] + Z_c
        dz = jnp.einsum("ngrcd,ncgrps->ndgrps", carry, ds_in,
                        precision=_HIGHEST)
        d_whole = jnp.exp(lt[..., -1]) * jnp.sum(dz * s_in, (-2, -1))

        # Z_c = sum_j (x_j to_end_j) (x) B_j, to_end_j = exp(l_Q - l_j) delta_j
        decay_end = jnp.exp(lt[..., -1:] - lt)
        to_end = decay_end * delta
        dz_t = dz.astype(t)
        dxe = dot("ncgrps,ncgsj->ncgrpj", dz_t, bh)
        db = dot("ncgrps,ncgrpj->ncgsj", dz_t,
                 (xh * to_end[..., None, :]).astype(t))
        d_to = jnp.sum(dxe * xh.astype(_F32), -2)
        # d(x to_end) in x's type from here, as jax's own backward pass has it
        dx = dxe.astype(t) * to_end[..., None, :]
        ddelta = d_to * decay_end
        d_end = d_to * to_end
        last = jnp.arange(q) == q - 1
        dl = dl - d_end + last * (jnp.sum(d_end, -1) + d_whole)[..., None]

        # inside a chunk: dM = dy x^T, one pass over it with M built again
        seen = jnp.tril(jnp.ones((q, q), bool))
        within = jnp.exp(jnp.where(seen, lt[..., :, None] - lt[..., None, :],
                                   -jnp.inf))               # (..,i,j)
        cb = dot("ncgsi,ncgsj->ncgij", ch, bh)[:, :, :, None]
        dm = dot("ncgrpi,ncgrpj->ncgrij", dyh, xh)
        # D's gradient, sum dy . x, is dM's diagonal
        dd = jnp.sum(dm * jnp.eye(q, dtype=_F32), (0, 1, 4, 5))
        delta_j = delta[..., None, :]
        per_delta = dm * cb * within                   # dM_ij M_ij / delta_j
        dx = dx + dot("ncgrpi,ncgrij->ncgrpj", dyh,
                      (cb * within * delta_j).astype(t))
        col = jnp.sum(per_delta, -2)                        # (n,c,g,r,j)
        ddelta = ddelta + col
        dl = dl + jnp.sum(per_delta * delta_j, -1) - col * delta
        # dM in x's type from here, as jax's own backward pass has it;
        # d(C B^T) (n,c,g,i,j)
        dcb = jnp.sum(dm.astype(t) * within * delta_j, 3).astype(t)
        dc = dc + dot("ncgsj,ncgij->ncgsi", bh, dcb)
        db = db + dot("ncgsi,ncgij->ncgsj", ch, dcb)

        # l_i = sum_{j <= i} delta_j A: d(delta_j A) = sum_{i >= j} dl_i
        dlam = _running(dl, seen.T)
        du = (ddelta + dlam * a) * jax.nn.sigmoid(u)
        dx = dx + d.astype(_F32).reshape(g, r, 1, 1) * dyf
        return (_unheads(dx.astype(t)), _unlane(du).astype(dt.dtype),
                (jnp.sum(dlam * delta, (0, 1, 4)) * a[..., 0]).reshape(h)
                .astype(a_log.dtype),
                ungroup(db).astype(b.dtype), ungroup(dc).astype(c.dtype),
                dd.reshape(h).astype(d.dtype),
                jnp.sum(du, (0, 1, 4)).reshape(h).astype(dt_bias.dtype))


_ssd_vjp = jax.custom_vjp(_ssd, nondiff_argnums=(7,))
# each rule jitted: one trace serves every Mamba-2 layer of a recipe at the
# same shapes
_ssd_vjp.defvjp(_ssd_fwd, jax.jit(_ssd_bwd, static_argnums=(0,)))
_ssd_jit = jax.jit(_ssd_vjp, static_argnums=(7,))


def ssd(x, dt, a_log, b, c, d, dt_bias, chunk: int):
    """x (N, S, H, P), dt (N, S, H) before its bias and softplus, a_log, d,
    dt_bias (H,), b, c (N, S, G, state) -> y (N, S, H, P) in x's type. S is
    whole chunks of `chunk`, or one shorter chunk, and H whole groups
    (proto/netshape.py `mamba2_problem` refuses the rest for the layer)."""
    return _ssd_jit(x, dt, a_log, b, c, d, dt_bias, chunk)
