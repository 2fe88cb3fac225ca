"""host-sync pass — device materialization inside a hot loop.

Port of tools/check_host_syncs.py (the framework's single-pass
ancestor; that file is now a deprecation shim delegating here) into
the pass framework, widened from its 7-module allowlist to the whole
tree. Dispatch is asynchronous: every device->host
materialization (`float()` / `np.asarray()` / `.item()` /
`jax.device_get`) blocks the host until the device catches up, and one
of those inside a loop serializes the dispatch pipeline (CLAUDE.md;
round 5 found a per-iteration `float()` in the gpipe clip path this
way).

Scope-aware where the ancestor was purely lexical: a function or
lambda *defined* inside a loop opens a new dynamic scope — its body
does not run once per loop iteration at definition time, so loop depth
resets there (the ancestor flagged closure bodies defined in loops;
per-file waiver noise at whole-tree scale would have drowned the
signal).

Static and approximate BY DESIGN: it cannot prove a value is a device
array, so it flags the call pattern and relies on waivers for the
deliberate cases (display-boundary materializations, host-side ndarray
normalization, text parsing). The waiver reason is part of the
contract: the author claims, in the diff, that the sync is intentional
and boundary-rate — or that the operand never lives on device.
"""

from __future__ import annotations

import ast
from typing import Iterator

from . import Finding, FileContext, LintPass, register

# call shapes that materialize a device value on the host
_NAME_CALLS = {"float"}                      # float(x)
_ATTR_CALLS = {                              # module.attr(x)
    ("np", "asarray"), ("np", "array"), ("numpy", "asarray"),
    ("numpy", "array"), ("jax", "device_get"),
}
_METHOD_CALLS = {"item"}                     # x.item()

# comprehensions/genexprs ARE loops: `[float(l) for l in losses]` pays
# one RTT per element just like the for-statement spelling
_LOOPS = (ast.For, ast.While, ast.AsyncFor,
          ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)

# a def/lambda body is a new dynamic scope: defining it inside a loop
# does not execute it inside the loop
_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def call_kind(node: ast.Call) -> str | None:
    fn = node.func
    # a literal operand is never a device value: float("nan"),
    # np.asarray(0.5) and friends are constant folding, not syncs
    if node.args and isinstance(node.args[0], ast.Constant):
        return None
    if isinstance(fn, ast.Name) and fn.id in _NAME_CALLS:
        return fn.id
    if isinstance(fn, ast.Attribute):
        if isinstance(fn.value, ast.Name) and (fn.value.id,
                                               fn.attr) in _ATTR_CALLS:
            return f"{fn.value.id}.{fn.attr}"
        if fn.attr in _METHOD_CALLS and not node.args:
            return f".{fn.attr}()"
    return None


@register
class HostSyncPass(LintPass):
    name = "host-sync"
    description = ("float()/np.asarray()/.item()/device_get inside a "
                   "loop — one blocking host sync per iteration")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        # candidate-first: scan the shared Call bucket, then climb
        # ancestors only for the handful of matching calls — the old
        # full-tree recursion (2 frames/node) dominated the 5 s
        # whole-tree budget
        for node in ctx.by_type(ast.Call):
            kind = call_kind(node)
            if kind is None:
                continue
            if self._loop_depth(ctx, node) > 0:
                stmt = ctx.stmt_of(node)
                yield Finding(
                    self.name, ctx.path, node.lineno,
                    f"{kind} inside a loop — a device value here "
                    "blocks the host once per iteration; keep it "
                    "on device, or waive with "
                    "`# lint: ok(host-sync) — reason` if the sync "
                    "is deliberate and boundary-rate (or the "
                    "operand is host data)",
                    span=(ctx.span_of(stmt) if stmt is not None
                          else None),
                    detail=kind)

    @staticmethod
    def _loop_depth(ctx: FileContext, node: ast.Call) -> int:
        """Dynamic loop depth of `node`: loop ancestors below the
        nearest enclosing def/lambda, minus loops whose evaluated-once
        iterable subtree contains `node` (a For's `iter` and a
        comprehension's first-generator source run before the first
        iteration, so they sit one level OUTSIDE their own loop)."""
        depth = 0
        child, parent = node, ctx.parent_of(node)
        while parent is not None:
            if isinstance(parent, _SCOPES):
                break
            if isinstance(parent, ast.While):
                # everything under a while — test included — runs per
                # iteration
                depth += 1
            elif isinstance(parent, (ast.For, ast.AsyncFor)):
                if child is not parent.iter:
                    depth += 1
            elif isinstance(parent, (ast.ListComp, ast.SetComp,
                                     ast.DictComp, ast.GeneratorExp)):
                gen0 = parent.generators[0]
                # `child` here is the comprehension field holding us —
                # the generators are not AST nodes, so the parent chain
                # jumps straight from iter/target/elt to the comp node;
                # containment in gen0.iter decides the evaluated-once
                # exemption
                it = gen0.iter
                rec = ctx._index()[1]
                me, span = rec.get(id(node)), rec.get(id(it))
                inside_iter = (me is not None and span is not None
                               and span[0] <= me[0] < span[1])
                if not inside_iter:
                    depth += 1
            child, parent = parent, ctx.parent_of(parent)
        return depth
