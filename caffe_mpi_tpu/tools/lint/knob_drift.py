"""knob-drift pass — performance knobs must be consumed, flagged, and
documented (the accepted-but-ignored detector).

ISSUE 6's trigger: `reduce_buckets` sat in the config schema for five
PRs as accepted-and-ignored (proto/config.py — the reference consumes
it in net.cpp:757-913, we silently didn't). A knob that parses but
drives nothing is worse than a missing one: recipes carry it, operators
tune it, and nothing changes. This pass holds every registered
performance knob to four legs at once:

  1. declared:  a `SolverParameter` dataclass field in
                caffe_mpi_tpu/proto/config.py (read by AST, no import)
  2. flagged:   spelled in caffe_mpi_tpu/tools/cli.py (the `caffe
                train` surface — a knob users cannot reach from the
                CLI is a solver-internal, not a knob)
  3. documented: named in docs/benchmarks.md (the perf-knob runbook)
  4. consumed:  READ somewhere under caffe_mpi_tpu/
                outside the schema, the CLI plumbing, and this lint
                package itself — a Load-context attribute access
                `.knob` or a `"knob"` string literal passed as a call
                argument (getattr / has checks). Writes (`sp.knob =
                args.knob` is plumbing, not consumption), docstring
                mentions, and this registry's own KNOBS tuple do NOT
                count. This is the leg whose absence means
                accept-and-ignore.

Like doc-drift, this is a whole-tree pass rooted at the run root;
roots without the schema/CLI/docs triple (fixture dirs) produce no
findings. Waive a leg on the knob's registry line below with
`# lint: ok(knob-drift) — reason` (e.g. a knob staged one PR before
its consumer).
"""

from __future__ import annotations

import ast
import os
from typing import Iterator

from . import FileContext, Finding, LintPass, iter_py_files, register

# the knob registry: solver-level execution-schedule/perf knobs, each
# required to satisfy all four legs. Extend this tuple when adding a
# knob (the docs/benchmarks.md section for it is then enforced too).
KNOBS = (
    "step_chunk",       # ISSUE 1: K-step fused training
    "test_chunk",       # ISSUE 2: fused async evaluation
    "reduce_overlap",   # ISSUE 6: overlapped bucketed reduction
    "reduce_buckets",   # ISSUE 6: bucket count
    "grad_bucket_mb",   # ISSUE 6: bucket byte budget
    "serve_window_ms",  # ISSUE 7: continuous-batching window
    "serve_buckets",    # ISSUE 7: AOT padded-batch bucket ladder
    "serve_hbm_mb",     # ISSUE 7: resident-model HBM budget (LRU spill)
    "precision",        # ISSUE 9: bf16 compute, f32 master weights
    "loss_scale",       # ISSUE 9: static/dynamic bf16 loss scaling
    "loss_scale_window",  # ISSUE 9: clean steps before scale regrowth
    "serve_dtype",      # ISSUE 9: bf16 serving bucket programs
    "decoded_cache_mb",  # ISSUE 10: bounded decoded-record cache tier
    "hosts",            # ISSUE 11: elastic multi-host cluster size
    "coordinator",      # ISSUE 11: coordination-service address
    "host_deadline",    # ISSUE 11: cross-host heartbeat deadline
    "serve_queue_limit",  # ISSUE 12: load-shedding admission control
    "serve_deadline_ms",  # ISSUE 12: per-request dispatch deadline
    "serve_stall_s",    # ISSUE 12: serving dispatch stall breaker
    "serve_decoded_cache_mb",  # ISSUE 14: hot-content request cache
    "serve_program_bank",  # ISSUE 17: persistent AOT program bank
    "serve_replicas",   # ISSUE 18: serving fleet size (replica procs)
    "serve_retry_budget",  # ISSUE 18: router sibling-retry budget
    "replica_deadline",  # ISSUE 18: replica heartbeat deadline
    "min_hosts",        # ISSUE 19: degraded-mode quorum floor
)

CONFIG_FILE = os.path.join("caffe_mpi_tpu", "proto", "config.py")
CLI_FILE = os.path.join("caffe_mpi_tpu", "tools", "cli.py")
DOCS_FILE = os.path.join("docs", "benchmarks.md")
# where a consumer read counts (schema + CLI plumbing excluded: writing
# `sp.knob = args.knob` is not consumption; the lint package excluded:
# its own KNOBS registry naming every knob must not satisfy the leg it
# enforces)
CONSUMER_SCAN = ("caffe_mpi_tpu",)
_EXCLUDED_CONSUMERS = (CONFIG_FILE, CLI_FILE)
_EXCLUDED_CONSUMER_DIRS = (os.path.join("caffe_mpi_tpu", "tools", "lint"),)


def _solver_fields(path: str) -> dict[str, int]:
    """{field_name: line} of SolverParameter's dataclass fields (plus
    NetParameter's net-level knobs and ServingParameter's serving-plane
    knobs, which count as declarations too), by AST — the pass must run
    without the package importable."""
    tree = ast.parse(open(path, encoding="utf-8").read(), filename=path)
    fields: dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name in (
                "SolverParameter", "NetParameter", "ServingParameter"):
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                        stmt.target, ast.Name):
                    fields.setdefault(stmt.target.id, stmt.lineno)
    return fields


def _mentions(src: str, knob: str) -> bool:
    return knob in src


def _reads(attrs, calls) -> set[str]:
    """Names the AST READS: Load-context `x.attr` attribute accesses,
    plus string literals passed as call arguments (getattr(sp, "knob"),
    sp.has("knob")). Store/Del-context attributes (`sp.knob = args.knob`
    — plumbing) and bare strings outside a call (docstrings, registry
    tuples) are excluded. Takes Attribute and Call node iterables
    (ctx.by_type buckets, or filtered ast.walk); one scan per file
    serves every knob."""
    reads: set[str] = set()
    for node in attrs:
        if isinstance(node.ctx, ast.Load):
            reads.add(node.attr)
    for node in calls:
        for a in node.args:
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                reads.add(a.value)
        for kw in node.keywords:
            a = kw.value
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                reads.add(a.value)
    return reads


@register
class KnobDriftPass(LintPass):
    name = "knob-drift"
    description = ("perf knobs (step_chunk/test_chunk/reduce_*) must be "
                   "declared, CLI-flagged, documented, and CONSUMED — "
                   "no accept-and-ignore")
    self_waiving = True   # applies registry-line waivers itself

    def check_tree(self, ctxs: list[FileContext],
                   root: str) -> Iterator[Finding]:
        cfg_path = os.path.join(root, CONFIG_FILE)
        cli_path = os.path.join(root, CLI_FILE)
        docs_path = os.path.join(root, DOCS_FILE)
        if not (os.path.isfile(cfg_path) and os.path.isfile(cli_path)
                and os.path.isfile(docs_path)):
            return
        fields = _solver_fields(cfg_path)
        cli_src = open(cli_path, encoding="utf-8").read()
        docs_src = open(docs_path, encoding="utf-8").read()

        # consumer scan: whole production tree, reusing parsed ctxs
        by_path = {c.path: c for c in ctxs}
        consumed: set[str] = set()
        for target in CONSUMER_SCAN:
            path = os.path.join(root, target)
            if not os.path.exists(path):
                continue
            for fp in iter_py_files([path]):
                rel = os.path.relpath(fp, root)
                if rel in _EXCLUDED_CONSUMERS or any(
                        rel == d or rel.startswith(d + os.sep)
                        for d in _EXCLUDED_CONSUMER_DIRS):
                    continue
                if consumed.issuperset(KNOBS):
                    break
                ctx = by_path.get(os.path.abspath(fp))
                if ctx is not None:
                    if ctx.tree is None:
                        continue
                    reads = _reads(ctx.by_type(ast.Attribute),
                                   ctx.by_type(ast.Call))
                else:
                    try:
                        nodes = list(ast.walk(ast.parse(
                            open(fp, encoding="utf-8").read())))
                    except SyntaxError:
                        continue
                    reads = _reads(
                        (n for n in nodes
                         if isinstance(n, ast.Attribute)),
                        (n for n in nodes if isinstance(n, ast.Call)))
                consumed.update(k for k in KNOBS if k in reads)

        cfg_ctx = by_path.get(os.path.abspath(cfg_path))
        waivers = cfg_ctx.waivers if cfg_ctx is not None else {}
        for knob in KNOBS:
            line = fields.get(knob, 1)

            def waived() -> bool:
                return self.name in waivers.get(line, ()) or \
                    self.name in waivers.get(line - 1, ())

            missing = []
            if knob not in fields:
                missing.append("a Solver/Net/ServingParameter field in "
                               + CONFIG_FILE)
            if not _mentions(cli_src, knob):
                missing.append("a CLI flag in " + CLI_FILE)
            if not _mentions(docs_src, knob):
                missing.append("documentation in " + DOCS_FILE)
            if knob not in consumed:
                missing.append(
                    "a consumer read under caffe_mpi_tpu/ — the knob "
                    "is accepted but IGNORED")
            if missing and not waived():
                yield Finding(
                    self.name, cfg_path, line,
                    f"knob {knob!r} is missing " + "; ".join(missing),
                    span=None)
