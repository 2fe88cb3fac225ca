"""traced-control-flow pass — Python branching on traced values.

Inside a function that gets traced (jit / lax.scan / lax.cond /
grad / vmap bodies), a Python `if`/`while`/`bool()`/`int()` applied to
a `jnp.`/`lax.` expression either raises ConcretizationError or — when
the value happens to be concrete at trace time — silently bakes the
branch into the compiled program and forces a retrace whenever it
flips. The reference never has this failure mode: its graph is the C++
call tree itself (net.cpp Forward/Backward run layer code directly, no
tracing). Here the blueprint is TensorFlow's whole-program validation
(PAPERS.md: OSDI'16) — check the program before dispatch, because
after dispatch is a live-TPU luxury this environment rarely has.

Reachability is a deliberately simple per-module over-approximation:

- roots: functions decorated with / passed to jit-like transforms
  (jit, pjit, grad, value_and_grad, vmap, pmap, checkpoint, remat,
  shard_map) and function-valued arguments of lax control-flow ops
  (scan, cond, while_loop, switch, fori_loop, map, associative_scan)
- edges: bare-name calls to functions defined in the same module
  (methods and cross-module calls are not chased)

Flagged inside reachable functions:

- `if`/`while`/ternary tests containing a `jnp.`/`lax.` call (minus a
  whitelist of trace-time-concrete metadata helpers: issubdtype,
  iinfo, finfo, ...)
- `bool(x)`/`int(x)` where x contains such a call

Both directions are approximate: a traced value held in a bare local
name is invisible (no type inference), and a host-only helper that
shares a name with a traced one is over-flagged — waive the latter
with `# lint: ok(traced-control-flow) — reason`.
"""

from __future__ import annotations

import ast
from typing import Iterator

from . import (Finding, FileContext, LintPass, attr_root, children,
               dotted_name, register)

# transforms whose function-valued arguments are traced
_TRANSFORMS = {"jit", "pjit", "grad", "value_and_grad", "vmap", "pmap",
               "checkpoint", "remat", "shard_map", "custom_vjp",
               "custom_jvp"}
_LAX_FLOW = {"scan", "cond", "while_loop", "switch", "fori_loop", "map",
             "associative_scan"}

# jnp/lax attributes that return trace-time-concrete metadata, not
# traced arrays — branching on them is normal and safe
_CONCRETE_ATTRS = {"issubdtype", "iinfo", "finfo", "result_type",
                   "promote_types", "dtype", "dtypes", "isdtype",
                   "canonicalize_dtype"}

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)

# substrings at least one of which must appear in a file's source for
# any root to exist there (every _TRANSFORMS/_LAX_FLOW spelling is a
# literal identifier in the call or decorator; `lax.map` needs a
# lax./jax. attribute root, so only the dotted forms are listed —
# `scan` also covers associative_scan)
_PREGATE_TOKENS = tuple(_TRANSFORMS) + (
    "scan", "cond", "while_loop", "switch", "fori_loop",
    "lax.map", "jax.map")


def _is_traced_namespace_call(node: ast.expr) -> ast.Call | None:
    """The first jnp./lax. call in the subtree that produces a traced
    value (metadata helpers excluded), else None."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        fn = sub.func
        if not isinstance(fn, ast.Attribute):
            continue
        root = attr_root(fn)
        full = dotted_name(fn) or ""
        if root in ("jnp", "lax") or full.startswith(("jax.numpy.",
                                                      "jax.lax.")):
            if fn.attr not in _CONCRETE_ATTRS:
                return sub
    return None


@register
class TracedControlFlowPass(LintPass):
    name = "traced-control-flow"
    description = ("Python if/while/bool()/int() on jnp/lax values "
                   "inside traced (jit/scan) functions")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # pregate: a finding needs a jnp./lax./jax.-rooted call (those
        # names appear literally in source) AND a traced root, whose
        # transform name does too — skip the two tree recursions for
        # files that can't possibly fire
        src = ctx.src
        if "jnp" not in src and "lax" not in src and "jax" not in src:
            return
        if not any(t in src for t in _PREGATE_TOKENS):
            return
        # ---- collect function definitions + call edges + roots -------
        funcs: list[dict] = []          # {node, name, calls}
        roots: set[int] = set()         # id(node) of traced roots
        by_name: dict[str, list[dict]] = {}

        def is_jitlike(expr: ast.expr) -> bool:
            """decorator / callee that traces its function argument —
            including the `partial(jax.jit, static_argnums=...)`
            idiom, where the transform hides one Call deeper."""
            target = expr.func if isinstance(expr, ast.Call) else expr
            name = dotted_name(target)
            if name is None:
                return False
            leaf = name.rsplit(".", 1)[-1]
            if leaf == "partial" and isinstance(expr, ast.Call) \
                    and expr.args:
                return is_jitlike(expr.args[0])
            return leaf in _TRANSFORMS

        def collect(node: ast.AST, current: dict | None,
                    stmt: ast.stmt | None = None) -> None:
            for child in children(node):
                s = child if isinstance(child, ast.stmt) else stmt
                if isinstance(child, _FUNC_DEFS + (ast.Lambda,)):
                    # `stmt` anchors waivers for lambda-body findings:
                    # a lambda has no statements of its own, so its
                    # findings waive on the enclosing statement
                    info = {"node": child, "calls": set(), "idx": len(funcs),
                            "name": getattr(child, "name", "<lambda>"),
                            "stmt": s}
                    funcs.append(info)
                    by_name.setdefault(info["name"], []).append(info)
                    if any(is_jitlike(d) for d in
                           getattr(child, "decorator_list", [])):
                        roots.add(id(child))
                    collect(child, info, s)
                    continue
                if isinstance(child, ast.Call):
                    callee = dotted_name(child.func)
                    if callee:
                        leaf = callee.rsplit(".", 1)[-1]
                        fn_args = ()
                        if leaf in _TRANSFORMS:
                            fn_args = child.args[:1]
                        elif leaf in _LAX_FLOW and attr_root(
                                child.func) in ("lax", "jax"):
                            fn_args = child.args
                        for a in list(fn_args) + [
                                kw.value for kw in child.keywords
                                if kw.arg in ("body", "cond", "f",
                                              "body_fun", "cond_fun",
                                              "fun")]:
                            if isinstance(a, ast.Name):
                                for info in by_name.get(a.id, []):
                                    roots.add(id(info["node"]))
                                if current is not None:
                                    current["calls"].add("__root__" + a.id)
                            elif isinstance(a, ast.Lambda):
                                roots.add(id(a))
                    if current is not None and isinstance(child.func,
                                                          ast.Name):
                        current["calls"].add(child.func.id)
                collect(child, current, s)

        collect(ctx.tree, None)

        # second chance for forward references: a Name passed to a
        # transform before its def was collected
        for info in funcs:
            for c in info["calls"]:
                if c.startswith("__root__"):
                    for target in by_name.get(c[len("__root__"):], []):
                        roots.add(id(target["node"]))

        # ---- propagate reachability over bare-name call edges --------
        reachable = {i for i, f in enumerate(funcs)
                     if id(f["node"]) in roots}
        changed = True
        while changed:
            changed = False
            for i, f in enumerate(funcs):
                if i not in reachable:
                    continue
                for callee in f["calls"]:
                    for target in by_name.get(callee, []):
                        j = target["idx"]
                        if j not in reachable:
                            reachable.add(j)
                            changed = True

        # ---- flag traced-value branching in reachable functions ------
        findings: list[Finding] = []

        def flag(node: ast.expr, what: str, stmt: ast.stmt | None) -> None:
            hit = _is_traced_namespace_call(node)
            if hit is None:
                return
            findings.append(Finding(
                self.name, ctx.path, node.lineno,
                f"{what} on a traced `{dotted_name(hit.func)}` value "
                "inside a jit/scan-reachable function — this forces "
                "concretization (ConcretizationError under jit, or a "
                "silent retrace per flip); use lax.cond/lax.select or "
                "hoist the decision to the host",
                span=ctx.span_of(stmt) if stmt is not None else None))

        consumed: set[int] = set()   # nodes inside an already-judged test

        def check_node(node: ast.AST, s: ast.stmt | None) -> None:
            """The flaggable shapes, applied to one node. A bool()/
            int() nested inside a flagged if/while test is the SAME
            defect — consume the test subtree so it reports once."""
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                what = ("ternary `if`" if isinstance(node, ast.IfExp)
                        else f"Python `{type(node).__name__.lower()}`")
                flag(node.test, what, s)
                consumed.update(id(n) for n in ast.walk(node.test))
            elif (isinstance(node, ast.Call)
                  and id(node) not in consumed
                  and isinstance(node.func, ast.Name)
                  and node.func.id in ("bool", "int")
                  and len(node.args) == 1):
                flag(node.args[0], f"`{node.func.id}()`", s)

        def scan_body(node: ast.AST, stmt: ast.stmt | None) -> None:
            for child in children(node):
                if isinstance(child, _FUNC_DEFS + (ast.Lambda,)):
                    continue  # nested scopes judged by their own entry
                s = child if isinstance(child, ast.stmt) else stmt
                check_node(child, s)
                scan_body(child, s)

        for i in sorted(reachable):
            node = funcs[i]["node"]
            body = node.body if isinstance(node.body, list) else [node.body]
            for stmt in body:
                # the body node itself (a lambda body can BE the
                # flaggable expression), then everything under it; a
                # lambda body's findings anchor waivers on the
                # statement enclosing the lambda
                s = stmt if isinstance(stmt, ast.stmt) else funcs[i]["stmt"]
                check_node(stmt, s)
                scan_body(stmt, s)

        # dedup (top-level If both flagged directly and via scan? no —
        # scan_body only sees children; direct flag covers the stmt
        # itself). Sort for stable output.
        findings.sort(key=lambda f: f.line)
        yield from findings
