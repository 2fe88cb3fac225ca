"""Learning-rate and momentum schedules.

Exact transcription of the reference's policy semantics
(src/caffe/solvers/sgd_solver.cpp:24-91 GetLearningRate/GetMomentum):
fixed/step/exp/inv/multistep/poly(+min_lr)/sigmoid, linear warmup ramp
(rampup_interval/rampup_lr — the large-batch training support), and
momentum policies fixed/poly/opt.

Everything is computed with jnp on a traced iteration scalar so the whole
schedule lives *inside* the jitted train step — no per-iteration recompiles
and no host round-trip, unlike the reference which computes rates on the CPU
each step.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..proto.config import SolverParameter


def learning_rate(p: SolverParameter, it: jnp.ndarray) -> jnp.ndarray:
    """lr(iter) as a traced f32 scalar."""
    itf = it.astype(jnp.float32)
    policy = p.lr_policy
    if policy == "fixed":
        rate = jnp.float32(p.base_lr)
    elif policy == "step":
        if p.stepsize <= 0:
            raise ValueError("step policy requires stepsize > 0")
        step = jnp.floor(itf / p.stepsize)
        rate = p.base_lr * jnp.power(p.gamma, step)
    elif policy == "exp":
        rate = p.base_lr * jnp.power(p.gamma, itf)
    elif policy == "inv":
        rate = p.base_lr * jnp.power(1.0 + p.gamma * itf, -p.power)
    elif policy == "multistep":
        bounds = jnp.asarray(p.stepvalue or [2**31 - 1], jnp.int32)
        step = jnp.searchsorted(bounds, it, side="right").astype(jnp.float32)
        rate = p.base_lr * jnp.power(p.gamma, step)
    elif policy == "poly":
        frac = 1.0 - itf / max(p.max_iter, 1)
        rate = p.min_lr + (p.base_lr - p.min_lr) * jnp.power(jnp.maximum(frac, 0.0),
                                                             p.power)
    elif policy == "sigmoid":
        rate = p.base_lr / (1.0 + jnp.exp(-p.gamma * (itf - p.stepsize)))
    else:
        raise ValueError(f"unknown lr_policy {policy!r}")
    if p.rampup_interval > 0:
        alpha = itf / p.rampup_interval
        ramp = p.rampup_lr + (p.base_lr - p.rampup_lr) * alpha
        rate = jnp.where(it < p.rampup_interval, ramp, rate)
    return rate.astype(jnp.float32)


def schedule(p: SolverParameter, it: jnp.ndarray
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lr, momentum) at iteration `it` — both pure jnp functions of a
    traced scalar, so the whole schedule evaluates INSIDE the jitted
    step (the iteration counter feeds straight in as a host scalar; no
    host round-trip, no recompiles as the iteration advances)."""
    return learning_rate(p, it), momentum(p, it)


def momentum(p: SolverParameter, it: jnp.ndarray) -> jnp.ndarray:
    """momentum(iter) as a traced f32 scalar."""
    itf = it.astype(jnp.float32)
    policy = p.momentum_policy
    if policy == "fixed":
        return jnp.float32(p.momentum)
    if policy == "poly":
        frac = itf / max(p.max_iter, 1)
        m = p.momentum + (p.max_momentum - p.momentum) * jnp.power(
            frac, p.momentum_power)
        return m.astype(jnp.float32)
    if policy == "opt":
        lr = learning_rate(p, it)
        m = jnp.square(1.0 - 0.5 * jnp.sqrt(lr))
        if p.has("max_momentum"):
            m = jnp.minimum(m, p.max_momentum)
        return m.astype(jnp.float32)
    raise ValueError(f"unknown momentum_policy {policy!r}")
