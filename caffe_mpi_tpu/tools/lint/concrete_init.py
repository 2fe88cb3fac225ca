"""concrete-init pass — traced init values in reduce_window / scan.

A `lax.reduce_window` whose init value is a traced scalar (e.g.
`jnp.zeros(())` under jit) cannot be recognised as the add/max monoid
and falls to the generic reduce_window primitive, which has no
reverse-mode rule: `jax.grad` fails with "Linearization failed to
produce known values for all output primals" (re-tested on jax 0.9.0,
PR 21; the shipped fix is ops/pool.py:73-76 — `np.zeros((), x.dtype)[()]`, a
concrete numpy scalar). The reference has no analogue: its pooling
backward is a hand-written kernel (src/caffe/layers/pooling_layer.cu)
with no AD to break. For `lax.scan`, carried arrays are normal — what
gets flagged is only the same hazard shape: a 0-d `jnp.` constructor
(`jnp.zeros(())`, `jnp.array(0.0)`) in the init slot, which should be
a Python/numpy literal scalar instead (same semantics, no traced
operand, no device transfer at trace time).

Approximate BY DESIGN: a bare name in the init slot is invisible (no
dataflow); the pass flags the constructor-in-slot pattern that caused
the documented breakage.
"""

from __future__ import annotations

import ast
from typing import Iterator

from . import Finding, FileContext, LintPass, attr_root, dotted_name, register

# jnp calls that return trace-time-concrete Python values, fine as inits
_CONCRETE_JNP = {"issubdtype", "iinfo", "finfo", "result_type",
                 "promote_types"}
_CTORS_0D = {"zeros", "ones", "full", "empty"}


def _jnp_rooted(fn: ast.expr) -> bool:
    if not isinstance(fn, ast.Attribute):
        return False
    root = attr_root(fn)
    full = dotted_name(fn) or ""
    return (root in ("jnp", "lax")
            or full.startswith(("jax.numpy.", "jax.lax.")))


def _traced_call_in(subtree) -> ast.Call | None:
    """Any jnp./lax. call in the subtree (metadata helpers excluded)."""
    for sub in subtree:
        if (isinstance(sub, ast.Call) and _jnp_rooted(sub.func)
                and sub.func.attr not in _CONCRETE_JNP):
            return sub
    return None


def _zero_d_ctor_in(subtree) -> ast.Call | None:
    """A 0-d jnp constructor in the subtree: jnp.zeros(()) /
    jnp.ones([]) / jnp.array(<number>)."""
    for sub in subtree:
        if not (isinstance(sub, ast.Call) and _jnp_rooted(sub.func)):
            continue
        attr = sub.func.attr
        if not sub.args:
            continue
        shape = sub.args[0]
        if attr in _CTORS_0D and isinstance(
                shape, (ast.Tuple, ast.List)) and not shape.elts:
            return sub
        if attr in ("array", "asarray") and isinstance(
                shape, (ast.Constant, ast.UnaryOp)):
            return sub
    return None


@register
class ConcreteInitPass(LintPass):
    name = "concrete-init"
    description = ("lax.reduce_window/lax.scan init values must be "
                   "concrete scalars, not traced jnp constructors")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ctx.by_type(ast.Call):
            if not isinstance(node.func, ast.Attribute):
                continue
            stmt = ctx.stmt_of(node)
            span = ctx.span_of(stmt) if stmt is not None else None
            if node.func.attr == "reduce_window":
                init = (node.args[1] if len(node.args) > 1 else
                        next((kw.value for kw in node.keywords
                              if kw.arg == "init_value"), None))
                if init is None:
                    continue
                hit = _traced_call_in(ctx.walk(init))
                if hit is not None:
                    yield Finding(
                        self.name, ctx.path, init.lineno,
                        "reduce_window init value is a traced "
                        f"`{dotted_name(hit.func)}` expression — the "
                        "generic reduce_window it lowers to has no "
                        "reverse-mode rule (jax.grad fails); use a "
                        "concrete scalar (literal, or "
                        "`np.zeros((), dtype)[()]` for a typed zero)",
                        span=span)
            elif (node.func.attr == "scan"
                  and attr_root(node.func) in ("lax", "jax")):
                init = (node.args[1] if len(node.args) > 1 else
                        next((kw.value for kw in node.keywords
                              if kw.arg == "init"), None))
                if init is None:
                    continue
                hit = _zero_d_ctor_in(ctx.walk(init))
                if hit is not None:
                    yield Finding(
                        self.name, ctx.path, hit.lineno,
                        "scan init carries a 0-d "
                        f"`{dotted_name(hit.func)}` constructor — "
                        "write the scalar as a Python/numpy literal "
                        "(same semantics, no traced operand; the "
                        "reduce_window variant of this pattern breaks "
                        "reverse-mode)",
                        span=span)
