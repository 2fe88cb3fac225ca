"""tpulint — whole-tree static analysis for TPU-hostile code patterns.

Replaces the compile-time safety net the reference stack gets for free
(C++ types + nvcc reject most of its bug classes at build time,
e.g. Makefile + src/caffe/CMakeLists.txt drive a type-checked build;
tools/check_host_syncs.py was this framework's single-pass ancestor).
In the JAX rebuild the costliest defects — a `float()` blocking the
host on the device once per loop iteration, a Python `if` on a traced
value, a traced `lax.reduce_window` init that has no reverse-mode rule
— compile fine and only surface at run time, some only on a chip. So
the checks run on the AST, before any dispatch, with no jax import:
the suite needs no device and costs seconds in tier-1.

Framework shape:

- every check is a `LintPass` subclass registered by `@register`; a
  pass implements `check(ctx)` (per file) and/or `check_tree(ctxs,
  root)` (cross-file, e.g. doc-drift)
- findings are waived per statement with a `lint: ok(<pass>) — reason`
  comment on any line of the statement's span or the line directly
  above; the reason is part of the contract — the author claims, in
  the diff, that the flagged pattern is deliberate
- the legacy `# host-sync: ok` spelling keeps working as a waiver for
  the host-sync pass (compat with pre-framework annotations)
- a waiver naming an unknown pass is itself a finding (bad-waiver):
  a misspelled waiver must fail the run, never silently suppress
- a waiver whose named pass no longer produces any finding on its
  statement is reported as stale (stale-waiver, default-on at the CLI,
  `--no-stale` to silence): the waiver inventory must not rot as
  passes and code evolve. Passes that apply waivers themselves
  (doc-drift, knob-drift — `self_waiving = True`) are exempt.
- CLI: `python -m caffe_mpi_tpu.tools.lint [--select P,...] [--json]
  [--changed REF] [--no-stale] [--profile] [paths...]`; default paths
  are the shipped tree (caffe_mpi_tpu/, tools/); `--changed
  REF` lints only files named by `git diff --name-only REF` (plus
  explicit paths) for fast pre-commit runs — a typo'd ref is a usage
  error (exit 2), never a false-clean exit 0; exit 1 on any finding;
  `--profile` reports per-pass wall-ms (and the shared-model build
  count) so the 5 s whole-tree budget stays attributable per pass

See docs/static_analysis.md for the pass catalog and how to add one.
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import os
import re
import sys
import tokenize
from dataclasses import dataclass
from typing import Iterable, Iterator

# ---------------------------------------------------------------------------
# findings + waivers

_WAIVER_RE = re.compile(r"#\s*lint:\s*ok\(([^)]*)\)")
_LEGACY_WAIVER_RE = re.compile(r"#\s*host-sync:\s*ok")


def children(node: ast.AST, _ast=ast.AST) -> list[ast.AST]:
    """`ast.iter_child_nodes(node)` as a list, in its order, without the
    two generators it runs a node: the AST walks of the whole-tree lint
    call it once a node, a million times."""
    out = []
    for name in node._fields:
        field = getattr(node, name, None)
        if isinstance(field, _ast):
            out.append(field)
        elif isinstance(field, list):
            out += [item for item in field if isinstance(item, _ast)]
    return out


def _waivers_in_comment(text: str) -> set[str]:
    names: set[str] = set()
    for m in _WAIVER_RE.finditer(text):
        names.update(n.strip() for n in m.group(1).split(",")
                     if n.strip())
    if _LEGACY_WAIVER_RE.search(text):
        names.add("host-sync")
    return names


def extract_waivers(src: str,
                    tree: "ast.Module | None" = None) -> dict[int, set[str]]:
    """{line: waived pass names} from the REAL comments of `src`.
    Waiver grammar quoted inside string literals or docstrings must
    NOT register as a waiver — text that merely *mentions* the grammar
    cannot suppress a finding on its statement. With a parsed `tree`
    the string spans come from its Constant/JoinedStr nodes (one cheap
    line scan instead of re-tokenizing the file — the tokenizer
    dominated the whole-tree run); without one (syntax-error files,
    direct callers) the tokenizer remains the arbiter."""
    waivers: dict[int, set[str]] = {}
    if "lint:" not in src and "host-sync:" not in src:
        # fast path: no waiver grammar anywhere
        return waivers
    if tree is None:
        try:
            tokens = tokenize.generate_tokens(io.StringIO(src).readline)
            comments = [(t.start[0], t.string) for t in tokens
                        if t.type == tokenize.COMMENT]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            comments = []    # unparseable files surface as 'syntax'
        for ln, text in comments:
            names = _waivers_in_comment(text)
            if names:
                waivers.setdefault(ln, set()).update(names)
        return waivers
    spans: list[tuple[int, int, int, int]] | None = None
    for ln0, line in enumerate(src.splitlines()):
        if "lint:" not in line and "host-sync:" not in line:
            continue
        if spans is None:
            # string-literal spans, collected only when a candidate
            # line exists (JoinedStr covers f-strings whole: pre-3.12
            # their inner Constant locations are unreliable)
            spans, stack = [], [tree]
            while stack:
                n = stack.pop()
                if (isinstance(n, ast.Constant)
                        and isinstance(n.value, (str, bytes))) \
                        or isinstance(n, ast.JoinedStr):
                    spans.append((n.lineno, n.col_offset, n.end_lineno,
                                  n.end_col_offset))
                stack += children(n)
        ln = ln0 + 1
        # the comment starts at the first '#' OUTSIDE every string
        # literal; everything after it is comment text
        idx = line.find("#")
        while idx != -1:
            if not any(l0 <= ln <= l1
                       and (ln, idx) >= (l0, c0) and (ln, idx) < (l1, c1)
                       for l0, c0, l1, c1 in spans):
                names = _waivers_in_comment(line[idx:])
                if names:
                    waivers.setdefault(ln, set()).update(names)
                break
            idx = line.find("#", idx + 1)
    return waivers


@dataclass
class Finding:
    """One lint violation. `span` is the (first, last) 1-based line range
    a waiver comment is honored on (None = unwaivable); `detail` is a
    short machine tag (e.g. the flagged call shape) for compat shims."""
    pass_name: str
    path: str
    line: int
    message: str
    span: tuple[int, int] | None = None
    detail: str = ""

    def format(self, root: str | None = None) -> str:
        path = os.path.relpath(self.path, root) if root else self.path
        return f"{path}:{self.line}: [{self.pass_name}] {self.message}"

    def as_dict(self, root: str | None = None) -> dict:
        path = os.path.relpath(self.path, root) if root else self.path
        return {"pass": self.pass_name, "path": path, "line": self.line,
                "message": self.message, "detail": self.detail}


def _build_index(n: ast.AST, stmt: ast.stmt | None, parent: ast.AST | None,
                 order: list, info: dict,
                 _iter=children, _stmt=ast.stmt) -> None:
    """Recursive DFS filling FileContext._index's (order, info): one
    append + one dict store per node keeps the whole-tree build inside
    the 5 s lint budget (the iterative tuple-stack version cost ~2x).
    Callers bump the recursion limit; AST depth tracks source nesting,
    not file size."""
    start = len(order)
    order.append(n)
    if isinstance(n, _stmt):
        stmt = n
    for c in _iter(n):
        _build_index(c, stmt, n, order, info)
    info[id(n)] = (start, len(order), stmt, parent)


_EMPTY_BUCKET: list[ast.AST] = []


class FileContext:
    """One parsed source file shared by all passes: source text, lines,
    AST (None on syntax error), and the per-line waiver map."""

    def __init__(self, path: str, root: str | None = None):
        self.path = os.path.abspath(path)
        self.root = root
        with open(path, encoding="utf-8") as f:
            self.src = f.read()
        self.lines = self.src.splitlines()
        self.tree: ast.Module | None = None
        self.syntax_error: SyntaxError | None = None
        try:
            self.tree = ast.parse(self.src, filename=path)
        except SyntaxError as e:
            self.syntax_error = e
        # line -> set of pass names waived on that line (real comments
        # only — quoted grammar in strings does not count)
        self.waivers: dict[int, set[str]] = extract_waivers(self.src,
                                                            self.tree)
        self._idx: tuple | None = None
        self._buckets: dict[type, list[ast.AST]] | None = None

    def _index(self) -> tuple:
        """(preorder, info) — ONE DFS over the file, shared by every
        pass: `info[id(n)] = (start, end, stmt, parent)` where
        `preorder[start:end]` is n's whole subtree (preorder keeps
        subtrees contiguous, unlike ast.walk's BFS), `stmt` is n's
        nearest enclosing statement, and `parent` its AST parent.
        Per-pass ast.walk re-traversals dominated the 5 s whole-tree
        budget; this makes every subtree query a list slice and every
        ancestor query a pointer chase."""
        if self._idx is None:
            order: list[ast.AST] = []
            info: dict[int, tuple] = {}
            if self.tree is not None:
                limit = sys.getrecursionlimit()
                sys.setrecursionlimit(max(limit, 20000))
                try:
                    _build_index(self.tree, None, None, order, info)
                finally:
                    sys.setrecursionlimit(limit)
            self._idx = (order, info)
        return self._idx

    def by_type(self, cls: type) -> list[ast.AST]:
        """All nodes of exact type `cls`, in preorder — built once for
        every node class on first use, so a pass that only cares about
        Call/Try/Attribute nodes scans thousands of nodes, not the
        whole 200k-node tree."""
        if self._buckets is None:
            buckets: dict[type, list[ast.AST]] = {}
            for n in self._index()[0]:
                t = type(n)
                b = buckets.get(t)
                if b is None:
                    buckets[t] = [n]
                else:
                    b.append(n)
            self._buckets = buckets
        return self._buckets.get(cls, _EMPTY_BUCKET)

    def parent_of(self, node: ast.AST) -> ast.AST | None:
        """AST parent of `node`, None for the root or nodes outside
        this file's tree."""
        rec = self._index()[1].get(id(node))
        return rec[3] if rec is not None else None

    def walk(self, node: ast.AST | None = None) -> list[ast.AST]:
        """All nodes of `node`'s subtree (default: the whole file) in
        DFS preorder, from the shared precomputed index. Nodes not in
        this file's tree (synthetic wrappers) fall back to ast.walk."""
        order, info = self._index()
        if node is None or node is self.tree:
            return order
        rec = info.get(id(node))
        if rec is None:
            return list(ast.walk(node))
        return order[rec[0]:rec[1]]

    def stmt_of(self, node: ast.AST) -> ast.stmt | None:
        """Nearest enclosing statement of `node` (itself if a stmt),
        None for nodes outside this file's tree."""
        rec = self._index()[1].get(id(node))
        return rec[2] if rec is not None else None

    @property
    def rel(self) -> str:
        """Path relative to the run root; absolute if outside it."""
        if self.root:
            r = os.path.relpath(self.path, self.root)
            if not r.startswith(".."):
                return r
        return self.path

    def span_of(self, stmt: ast.stmt | ast.expr) -> tuple[int, int]:
        """Waiver-search span for a node: its own line range. `waived`
        additionally honors a comment-ONLY line directly above. For a
        compound statement (if/while/for/with/def) the span is the
        HEADER only — a waiver on some nested body statement must not
        silently suppress a finding anchored to the header."""
        end = getattr(stmt, "end_lineno", None) or stmt.lineno
        body = getattr(stmt, "body", None)
        if isinstance(body, list) and body and isinstance(body[0],
                                                          ast.stmt):
            # header end = end of the test/iter expression (NOT
            # body[0].lineno - 1: a comment line between header and
            # body must not fall inside the header span)
            hdr = stmt.lineno
            for attr in ("test", "iter", "items"):
                v = getattr(stmt, attr, None)
                for n in (v if isinstance(v, list)
                          else [v] if v is not None else []):
                    hdr = max(hdr, getattr(n, "end_lineno", 0) or 0)
            end = min(end, hdr)
        return (stmt.lineno, end)

    def comment_only(self, ln: int) -> bool:
        text = self.lines[ln - 1] if 0 < ln <= len(self.lines) else ""
        return text.lstrip().startswith("#")

    def waiver_lines(self, span: tuple[int, int] | None,
                     pass_name: str) -> list[int]:
        """Lines whose waiver suppresses a finding with this span (see
        `span_waiver_lines` — ONE implementation of the binding
        contract, shared with passes that self-apply waivers). The
        caller records these as HONORED so stale-waiver detection knows
        which waivers still earn their keep."""
        if span is None:
            return []
        return span_waiver_lines(span, pass_name, self.waivers,
                                 self.lines)

    def waived(self, span: tuple[int, int] | None, pass_name: str) -> bool:
        return bool(self.waiver_lines(span, pass_name))


def span_waiver_lines(span: tuple[int, int], pass_name: str,
                      waivers: dict[int, set[str]],
                      lines: list[str]) -> list[int]:
    """THE waiver-binding contract, in one place (FileContext and the
    self-waiving passes both delegate here — two copies of this walk
    drifted once and must not again): a waiver binds anywhere in the
    statement's span (trailing comments included), or anywhere in the
    contiguous COMMENT-ONLY block directly above it (a multi-line
    waiver comment binds to the statement it precedes; a trailing
    waiver on the PREVIOUS statement is not comment-only and so cannot
    leak onto the next one)."""
    lo, hi = span
    out = [ln for ln in range(lo, hi + 1)
           if pass_name in waivers.get(ln, ())]
    above = lo - 1
    while 1 <= above <= len(lines) \
            and lines[above - 1].lstrip().startswith("#"):
        if pass_name in waivers.get(above, ()):
            out.append(above)
        above -= 1
    return out


# ---------------------------------------------------------------------------
# pass registry

class LintPass:
    """Base class. Subclasses set `name` + `description` and override
    `check` (per-file) and/or `check_tree` (whole-run, for cross-file
    invariants). Yield `Finding`s; the framework applies waivers.
    Passes that apply waivers THEMSELVES (whole-tree scans over files
    the caller didn't select, e.g. doc-drift) set `self_waiving = True`
    so stale-waiver detection does not misread their waivers as dead."""

    name: str = ""
    description: str = ""
    self_waiving: bool = False

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        return iter(())

    def check_tree(self, ctxs: list[FileContext],
                   root: str) -> Iterator[Finding]:
        return iter(())


REGISTRY: dict[str, LintPass] = {}


def register(cls: type[LintPass]) -> type[LintPass]:
    inst = cls()
    assert inst.name and inst.name not in REGISTRY, inst.name
    REGISTRY[inst.name] = inst
    return cls


def _load_passes() -> None:
    # import for side effect: each module registers its pass(es)
    from . import (concrete_init, concurrency, doc_drift,  # noqa: F401
                   failure_path, gated_imports, host_sync, knob_drift,
                   netlint, reference_citation, traced_flow)


# ---------------------------------------------------------------------------
# tree walking + running

def repo_root() -> str:
    """The directory holding the caffe_mpi_tpu package."""
    pkg = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.dirname(pkg)


DEFAULT_SCAN = ("caffe_mpi_tpu", "tools")


def iter_py_files(paths: Iterable[str]) -> Iterator[str]:
    for target in paths:
        if os.path.isdir(target):
            for dirpath, dirnames, files in os.walk(target):
                dirnames[:] = sorted(d for d in dirnames
                                     if d != "__pycache__")
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(dirpath, name)
        elif target.endswith(".py"):
            yield target


def _bad_waiver_findings(ctx: FileContext,
                         known: set[str]) -> Iterator[Finding]:
    for ln, names in sorted(ctx.waivers.items()):
        for name in sorted(names - known):
            yield Finding(
                "bad-waiver", ctx.path, ln,
                f"waiver names unknown pass {name!r} (known: "
                f"{', '.join(sorted(known))}) — a misspelled waiver "
                "suppresses nothing", span=None)


def run_lint(paths: Iterable[str] | None = None,
             select: Iterable[str] | None = None,
             root: str | None = None,
             stale: bool = False,
             profile: dict | None = None) -> list[Finding]:
    """Run the selected passes (default: all) over `paths` (default:
    the shipped tree under `root`). Returns waiver-filtered findings,
    ordered by path then line. `stale=True` (the CLI default; library
    default off for fixture ergonomics) additionally reports every
    waiver in the scanned files whose named pass — when selected and
    not self-waiving — no longer suppresses any finding on its
    statement. `profile`, when a dict, is filled with per-pass wall-ms
    (`passes`), file count (`files`), total ms (`total_ms`), and the
    number of shared concurrency-model builds this run performed
    (`model_builds` — the interprocedural passes must share ONE)."""
    import time
    _load_passes()
    root = root or repo_root()
    t_run0 = time.perf_counter()
    prof_ms: dict[str, float] = {}
    builds0 = 0
    if profile is not None:
        from .concurrency import BUILD_COUNT
        builds0 = BUILD_COUNT[0]
    if paths is None:
        # default-scan entries are filtered by existence (a fixture
        # root need not model tools/); EXPLICIT paths must exist —
        # a typo'd CI path silently reporting "clean" is the one
        # failure mode a tripwire cannot afford
        paths = [p for p in (os.path.join(root, t) for t in DEFAULT_SCAN)
                 if os.path.exists(p)]
    else:
        paths = list(paths)
        bad = [p for p in paths
               if not os.path.exists(p)
               or (os.path.isfile(p) and not p.endswith(".py"))]
        if bad:
            raise FileNotFoundError(
                f"lint path(s) do not exist or are not .py: {bad}")
    if select is None:
        passes = list(REGISTRY.values())
    else:
        unknown = [s for s in select if s not in REGISTRY]
        if unknown:
            # ValueError, not KeyError: main() maps this to a usage
            # error, and a broad KeyError catch would also swallow
            # genuine pass bugs as exit 2
            raise ValueError(
                f"unknown pass(es) {unknown}; known: {sorted(REGISTRY)}")
        passes = [REGISTRY[s] for s in select]
    selected = {p.name for p in passes}

    ctxs: list[FileContext] = []
    findings: list[Finding] = []
    # (path, line, pass) of every waiver that suppressed a finding —
    # the evidence stale-waiver detection subtracts from the inventory
    honored: set[tuple[str, int, str]] = set()
    for path in iter_py_files(paths):
        ctx = FileContext(path, root=root)
        if ctx.syntax_error is not None:
            e = ctx.syntax_error
            findings.append(Finding(
                "syntax", ctx.path, e.lineno or 0,
                f"SYNTAX ERROR: {e.msg}", span=None,
                detail=f"SYNTAX ERROR: {e.msg}"))
            continue
        ctxs.append(ctx)
        findings.extend(_bad_waiver_findings(ctx, set(REGISTRY)))
        for p in passes:
            t0 = time.perf_counter() if profile is not None else 0.0
            for f in p.check(ctx):
                lines = ctx.waiver_lines(f.span, p.name)
                if lines:
                    honored.update((ctx.path, ln, p.name)
                                   for ln in lines)
                else:
                    findings.append(f)
            if profile is not None:
                prof_ms[p.name] = prof_ms.get(p.name, 0.0) \
                    + (time.perf_counter() - t0) * 1000.0
    for p in passes:
        t0 = time.perf_counter() if profile is not None else 0.0
        findings.extend(p.check_tree(ctxs, root))
        if profile is not None:
            prof_ms[p.name] = prof_ms.get(p.name, 0.0) \
                + (time.perf_counter() - t0) * 1000.0
    # tree findings from files in ctxs honor waivers too
    by_path = {c.path: c for c in ctxs}
    kept = []
    for f in findings:
        if f.pass_name in selected and f.path in by_path:
            lines = by_path[f.path].waiver_lines(f.span, f.pass_name)
            if lines:
                honored.update((f.path, ln, f.pass_name)
                               for ln in lines)
                continue
        kept.append(f)
    findings = kept
    if stale:
        # a waiver for a selected, non-self-waiving pass that matched
        # no finding suppresses nothing — the inventory is rotting
        eligible = {p.name for p in passes if not p.self_waiving}
        for ctx in ctxs:
            for ln in sorted(ctx.waivers):
                for name in sorted(ctx.waivers[ln] & eligible):
                    if (ctx.path, ln, name) not in honored:
                        findings.append(Finding(
                            "stale-waiver", ctx.path, ln,
                            f"stale waiver: pass {name!r} reports no "
                            "finding on this statement any more — "
                            "remove the waiver (or run with "
                            "--no-stale to silence this check)",
                            span=None, detail=name))
    findings.sort(key=lambda f: (f.path, f.line, f.pass_name))
    if profile is not None:
        from .concurrency import BUILD_COUNT
        profile["passes"] = {n: round(ms, 3)
                             for n, ms in sorted(prof_ms.items())}
        profile["files"] = len(ctxs)
        profile["total_ms"] = round(
            (time.perf_counter() - t_run0) * 1000.0, 3)
        profile["model_builds"] = BUILD_COUNT[0] - builds0
    return findings


def run_pass_on_file(pass_name: str, path: str,
                     root: str | None = None) -> list[Finding]:
    """One pass over one file (compat-shim entry point). Syntax errors
    come back as a single 'syntax' finding."""
    _load_passes()
    ctx = FileContext(path, root=root or repo_root())
    if ctx.syntax_error is not None:
        e = ctx.syntax_error
        return [Finding("syntax", ctx.path, e.lineno or 0,
                        f"SYNTAX ERROR: {e.msg}", span=None,
                        detail=f"SYNTAX ERROR: {e.msg}")]
    p = REGISTRY[pass_name]
    return [f for f in p.check(ctx) if not ctx.waived(f.span, p.name)]


# ---------------------------------------------------------------------------
# shared AST helpers used by several passes

def attr_root(node: ast.expr) -> str | None:
    """Base name of a dotted chain: `lax.scan` -> 'lax',
    `jax.lax.scan` -> 'jax'. None for anything not Name-rooted."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def dotted_name(node: ast.expr) -> str | None:
    """Full dotted spelling of a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


# ---------------------------------------------------------------------------
# CLI

def main(argv: list[str] | None = None) -> int:
    _load_passes()
    ap = argparse.ArgumentParser(
        prog="python -m caffe_mpi_tpu.tools.lint",
        description="tpulint — static analysis for TPU-hostile patterns")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: shipped tree)")
    ap.add_argument("--select", default=None,
                    help="comma-separated pass names (default: all)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON array")
    ap.add_argument("--list", action="store_true", dest="list_passes",
                    help="list registered passes and exit")
    ap.add_argument("--changed", metavar="REF", default=None,
                    help="lint only .py files named by `git diff "
                         "--name-only REF` (plus explicit paths) — "
                         "fast pre-commit mode; a bad REF exits 2")
    ap.add_argument("--no-stale", action="store_true", dest="no_stale",
                    help="skip stale-waiver detection (waivers whose "
                         "pass no longer fires on their statement)")
    ap.add_argument("--profile", action="store_true", dest="profile",
                    help="report per-pass wall-ms (text: stderr table; "
                         "--json: a {findings, profile} object) so the "
                         "5 s whole-tree budget stays attributable")
    args = ap.parse_args(argv)
    if args.list_passes:
        for name in sorted(REGISTRY):
            print(f"{name:22s} {REGISTRY[name].description}")
        return 0
    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    root = repo_root()
    paths = list(args.paths)
    if args.changed is not None:
        import subprocess
        try:
            proc = subprocess.run(
                ["git", "diff", "--name-only", args.changed, "--"],
                cwd=root, capture_output=True, text=True, timeout=60)
        except subprocess.TimeoutExpired:
            # a wedged git (dead NFS, lock contention) must surface as
            # a usage error, not hang the pre-commit hook forever
            sys.stderr.write(f"git diff --name-only {args.changed} "
                             "timed out after 60s\n")
            return 2
        if proc.returncode != 0:
            # a typo'd ref MUST be a usage error, never a false-clean
            # exit 0 with zero files scanned
            sys.stderr.write(proc.stderr or
                             f"git diff --name-only {args.changed} "
                             "failed\n")
            return 2
        # only files the default scan would cover: tests/ and examples/
        # are deliberately OUTSIDE the lint contract (torch-oracle
        # host syncs etc.), and a pre-commit run must not fail on code
        # the full-tree run deliberately exempts
        dir_roots = tuple(t + "/" for t in DEFAULT_SCAN
                          if not t.endswith(".py"))
        changed = [os.path.join(root, rel)
                   for rel in (line.strip()
                               for line in proc.stdout.splitlines())
                   if rel.endswith(".py")
                   and (rel in DEFAULT_SCAN
                        or rel.startswith(dir_roots))]
        # deleted files appear in the diff but no longer exist; new
        # UNTRACKED files never appear — document, don't guess
        paths.extend(p for p in changed if os.path.exists(p))
        # model edits (ISSUE 15): a changed prototxt under models/ or
        # examples/, or the zoo generator itself, triggers the net-*
        # passes — whole-model-tree (they are whole-tree passes, and
        # the per-run analysis cache keeps that cheap), which covers
        # the affected models a fortiori
        from .netlint import MODEL_SCAN, NET_PASSES
        model_dirs = tuple(d + "/" for d in MODEL_SCAN)
        model_changed = [
            rel for rel in (line.strip()
                            for line in proc.stdout.splitlines())
            if (rel.endswith(".prototxt") and rel.startswith(model_dirs))
            or rel == "models/generate_models.py"]
        if not paths and model_changed:
            # prototxt-only change: run just the net-* family over no
            # .py files at all (unless the user already narrowed with
            # --select) — the passes scan the model tree themselves
            if select is None:
                select = list(NET_PASSES)
            try:
                findings = run_lint([], select=select, root=root)
            except ValueError as e:
                print(e.args[0], file=sys.stderr)
                return 2
            return _emit(findings, root, args.as_json)
        if not paths:
            # the --json contract promises a JSON array on stdout even
            # on this fast path — prose goes to stderr
            if args.as_json:
                print("[]")
            print("lint --changed: no changed python or model files in "
                  "the scanned tree (" + ", ".join(DEFAULT_SCAN)
                  + ", " + ", ".join(MODEL_SCAN) + ")",
                  file=sys.stderr)
            return 0
    profile = {} if args.profile else None
    try:
        findings = run_lint(paths or None, select=select, root=root,
                            stale=not args.no_stale, profile=profile)
    except (ValueError, FileNotFoundError) as e:
        print(e.args[0], file=sys.stderr)
        return 2
    return _emit(findings, root, args.as_json, profile=profile)


def _emit(findings: list[Finding], root: str, as_json: bool,
          profile: dict | None = None) -> int:
    if as_json:
        if profile is not None:
            # --json alone keeps the bare-array contract; --profile
            # opts into the {findings, profile} envelope explicitly
            print(json.dumps({"findings": [f.as_dict(root)
                                           for f in findings],
                              "profile": profile}, indent=1))
        else:
            print(json.dumps([f.as_dict(root) for f in findings],
                             indent=1))
    else:
        for f in findings:
            print(f.format(root))
        if profile is not None:
            print(f"lint --profile: {profile.get('files', 0)} files, "
                  f"{len(profile.get('passes', {}))} passes, "
                  f"{profile.get('model_builds', 0)} shared model "
                  f"build(s), {profile.get('total_ms', 0.0):.0f} ms "
                  "total", file=sys.stderr)
            for name, ms in sorted(profile.get("passes", {}).items(),
                                   key=lambda kv: -kv[1]):
                print(f"  {name:24s} {ms:8.1f} ms", file=sys.stderr)
    if findings:
        print(f"{len(findings)} lint finding(s)", file=sys.stderr)
        return 1
    return 0
