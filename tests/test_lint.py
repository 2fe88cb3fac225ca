"""tpulint framework (ISSUE 5): every pass catches its seeded bug,
honors its waiver, and a misspelled waiver still fails; the shipped
tree is lint-clean, fast, and checkable without jax or a device.

Fixture convention: per pass, one file seeding a known violation and
one seeding the same pattern waived with
`# lint: ok(<pass>) — reason`.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

from caffe_mpi_tpu.tools import lint

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_PASSES = ("host-sync", "traced-control-flow", "concrete-init",
              "gated-imports", "reference-citation", "doc-drift",
              "knob-drift", "lock-order", "blocking-under-lock",
              "thread-shared-mutation",
              # ISSUE 15: model-level passes (tests/test_netlint.py)
              "net-wiring", "net-shape", "net-params", "net-dtype",
              "net-serve", "net-footprint",
              # ISSUE 20: failure-path family
              "future-resolution", "typed-failure", "thread-crash",
              "deadline-discipline")


def _write(tmp_path, name, src):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return str(p)


def _run(paths, select, root=None):
    return lint.run_lint(paths=paths, select=list(select),
                         root=root or _ROOT)


def _names(findings):
    return sorted({f.pass_name for f in findings})


# ---------------------------------------------------------------------------
# registry + CLI surface

def test_all_tentpole_passes_registered():
    lint._load_passes()
    for name in ALL_PASSES:
        assert name in lint.REGISTRY, name
        assert lint.REGISTRY[name].description
    # the documented suite size (CLAUDE.md / docs/static_analysis.md):
    # ten code passes + six net-* model passes + the four ISSUE 20
    # failure-path passes, nothing registered twice or forgotten
    assert len(lint.REGISTRY) == 20, sorted(lint.REGISTRY)


def test_shipped_tree_is_clean_fast_and_jax_free():
    """`python -m caffe_mpi_tpu.tools.lint` exits 0 on the shipped
    tree, in under 5 s, with jax imports poisoned — the whole suite
    needs no device."""
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "for m in ('jax', 'jaxlib'):\n"
         "    sys.modules[m] = None\n"  # any `import jax` now raises
         "from caffe_mpi_tpu.tools.lint import main\n"
         "raise SystemExit(main([]))"],
        cwd=_ROOT, capture_output=True, text=True, timeout=60,
        env=dict(os.environ, PYTHONPATH=_ROOT))
    elapsed = time.monotonic() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert elapsed < 5.0, f"lint took {elapsed:.1f}s (budget 5s)"


def test_cli_select_unknown_pass_is_usage_error():
    assert lint.main(["--select", "no-such-pass"]) == 2


def test_cli_nonexistent_path_is_usage_error_not_false_clean(capsys):
    """A typo'd path must NOT exit 0 ('clean') — that is the one
    failure mode a tripwire cannot afford — nor crash with a raw
    traceback."""
    assert lint.main(["caffe_mpi_tpuu"]) == 2       # typo'd dir
    assert lint.main(["no_such_file.py"]) == 2
    err = capsys.readouterr().err
    assert "do not exist" in err


def test_default_scan_tolerates_roots_without_tools(tmp_path):
    """run_lint(root=fixture) must not crash when the root lacks
    DEFAULT_SCAN entries like tools/."""
    _write(tmp_path, "caffe_mpi_tpu/ok.py", """
        '''Replaces nothing.py:1 — fixture.'''
    """)
    assert lint.run_lint(root=str(tmp_path)) == []


def test_cli_json_output(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        def f(xs):
            return [float(x) for x in xs]
    """)
    rc = lint.main(["--select", "host-sync", "--json", bad])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out and out[0]["pass"] == "host-sync"
    assert out[0]["line"] == 3


def test_syntax_error_is_surfaced_not_swallowed(tmp_path):
    p = _write(tmp_path, "broken.py", "def oops(:\n")
    findings = _run([p], ["host-sync"])
    assert len(findings) == 1
    assert findings[0].pass_name == "syntax"
    assert "SYNTAX ERROR" in findings[0].message


# ---------------------------------------------------------------------------
# host-sync

def test_host_sync_catches_seeded_bug(tmp_path):
    p = _write(tmp_path, "hot.py", """
        import numpy as np

        def train(losses):
            total = 0.0
            for l in losses:
                total += float(l)
            while losses:
                x = np.asarray(losses.pop())
                y = losses[0].item()
            return total, float(total)     # outside any loop: clean
    """)
    kinds = sorted(f.detail for f in _run([p], ["host-sync"]))
    assert kinds == [".item()", "float", "np.asarray"]


def test_host_sync_honors_waiver_and_legacy_spelling(tmp_path):
    p = _write(tmp_path, "waived.py", """
        import numpy as np

        def display(window):
            for l in window:
                s = float(l)  # lint: ok(host-sync) — display boundary
                v = np.asarray(l)  # host-sync: ok (legacy spelling)
    """)
    assert _run([p], ["host-sync"]) == []


def test_host_sync_scope_aware(tmp_path):
    """A function/lambda DEFINED inside a loop is a new dynamic scope
    (not executed per iteration at def time), and a for-loop's iterable
    is evaluated once — neither is a per-iteration sync. Calls inside
    the defined function still count when IT loops."""
    p = _write(tmp_path, "scopes.py", """
        import numpy as np

        def build(schedule, blobs):
            cbs = []
            for s in schedule:
                def cb(v):
                    return float(v)        # def-time: not in the loop
                cbs.append(cb)
            for row in np.asarray(blobs):  # iterable: evaluated once
                pass
            def worker(vals):
                return [v.item() for v in vals]   # still a real loop
            return cbs, worker
    """)
    findings = _run([p], ["host-sync"])
    assert [(f.line, f.detail) for f in findings] == [(13, ".item()")]


def test_host_sync_comprehension_as_for_iterable_still_counts(tmp_path):
    """A comprehension used AS a for-loop's iterable is evaluated once
    but still loops over its own elements — the per-element sync must
    not escape through the for-header position."""
    p = _write(tmp_path, "itercomp.py", """
        def drain(losses):
            total = 0.0
            for l in [float(x) for x in losses]:
                total += l
            for l in sum(v.item() for v in losses):   # nested in call
                total += l
            return total
    """)
    kinds = sorted(f.detail for f in _run([p], ["host-sync"]))
    assert kinds == [".item()", "float"]


# ---------------------------------------------------------------------------
# traced-control-flow

_TRACED_BAD = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        if jnp.sum(x) > 0:
            x = x + 1
        n = int(jnp.max(x))
        return helper(x), n

    def helper(x):
        while jnp.any(x > 0):
            x = x - 1
        return x

    def host_only(x):
        if jnp.sum(x) > 0:     # not reachable from any traced root
            return x
        return -x
"""


def test_traced_control_flow_catches_seeded_bug(tmp_path):
    p = _write(tmp_path, "traced.py", _TRACED_BAD)
    findings = _run([p], ["traced-control-flow"])
    lines = sorted(f.line for f in findings)
    assert lines == [7, 9, 13]   # if, int(), while-in-callee; host_only clean


def test_traced_control_flow_honors_waiver_and_whitelist(tmp_path):
    p = _write(tmp_path, "waived.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, training):
            # lint: ok(traced-control-flow) — static arg, concrete at trace
            if jnp.asarray(training):
                x = x + 1
            if jnp.issubdtype(x.dtype, jnp.floating):  # metadata: fine
                x = x * 2
            return x
    """)
    assert _run([p], ["traced-control-flow"]) == []


def test_traced_control_flow_sees_scan_bodies(tmp_path):
    p = _write(tmp_path, "scanbody.py", """
        from jax import lax
        import jax.numpy as jnp

        def outer(xs):
            def body(carry, x):
                if jnp.abs(x) > 1:       # traced: scan body
                    carry = carry + x
                return carry, x
            return lax.scan(body, 0.0, xs)
    """)
    findings = _run([p], ["traced-control-flow"])
    assert [f.line for f in findings] == [7]


# ---------------------------------------------------------------------------
# concrete-init

def test_concrete_init_catches_seeded_bug(tmp_path):
    p = _write(tmp_path, "init.py", """
        import numpy as np
        import jax.numpy as jnp
        from jax import lax

        def bad_pool(x):
            return lax.reduce_window(x, jnp.zeros(()), lax.add,
                                     window_dimensions=(1,),
                                     window_strides=(1,),
                                     padding=((0, 0),))

        def good_pool(x):
            return lax.reduce_window(x, np.zeros((), x.dtype)[()],
                                     lax.add, window_dimensions=(1,),
                                     window_strides=(1,),
                                     padding=((0, 0),))

        def bad_scan(xs):
            return lax.scan(lambda c, x: (c + x, c), jnp.zeros(()), xs)

        def good_scan(acc0, xs):
            return lax.scan(lambda c, x: (c + x, c), acc0, xs)
    """)
    findings = _run([p], ["concrete-init"])
    assert sorted(f.line for f in findings) == [7, 19]


def test_concrete_init_honors_waiver(tmp_path):
    p = _write(tmp_path, "waived.py", """
        import jax.numpy as jnp
        from jax import lax

        def pool(x):
            # lint: ok(concrete-init) — forward-only op, never differentiated
            return lax.reduce_window(x, jnp.zeros(()), lax.max,
                                     window_dimensions=(1,),
                                     window_strides=(1,),
                                     padding=((0, 0),))
    """)
    assert _run([p], ["concrete-init"]) == []


# ---------------------------------------------------------------------------
# gated-imports

def test_gated_imports_catches_seeded_bug(tmp_path):
    p = _write(tmp_path, "db.py", """
        import lmdb

        def open_db(path):
            return lmdb.open(path)
    """)
    findings = _run([p], ["gated-imports"])
    assert len(findings) == 1 and findings[0].line == 2


def test_gated_imports_honors_gate_waiver_and_tests_exemption(tmp_path):
    gated = _write(tmp_path, "gated.py", """
        try:
            import lmdb
        except ImportError:
            lmdb = None

        import flask  # lint: ok(gated-imports) — demo-only module

        def ready():
            return lmdb is not None
    """)
    in_tests = _write(tmp_path, "tests/test_oracle.py", """
        import torch

        def test_x():
            assert torch is not None
    """)
    assert _run([gated, in_tests], ["gated-imports"]) == []


# ---------------------------------------------------------------------------
# reference-citation

def test_reference_citation_catches_seeded_bug(tmp_path):
    p = _write(tmp_path, "mod.py", '''
        """A module docstring that cites nothing."""

        def f():
            return 1
    ''')
    findings = _run([p], ["reference-citation"])
    assert len(findings) == 1 and findings[0].line == 2


def test_reference_citation_honors_waiver_citation_and_trivial(tmp_path):
    waived = _write(tmp_path, "native.py", '''
        # lint: ok(reference-citation) — TPU-native, no reference analogue
        """A genuinely new subsystem."""

        def f():
            return 1
    ''')
    cited = _write(tmp_path, "cited.py", '''
        """Replaces src/caffe/solver.cpp:187-351 with a fused step.

        Brace-group citations like src/caffe/layers/{relu,elu}_layer.{cpp,cu}
        count too.
        """

        def f():
            return 1
    ''')
    trivial = _write(tmp_path, "__init__.py", """
        from os import path
        X = 1
    """)
    assert _run([waived, cited, trivial], ["reference-citation"]) == []


# ---------------------------------------------------------------------------
# doc-drift (needs a mini tree: registry + docs + call sites)

def _mini_tree(tmp_path, extra_call="", ghost_entry=False):
    ghost = '\n            "ghost_site": "never fired",' if ghost_entry \
        else ""
    _write(tmp_path, "caffe_mpi_tpu/utils/resilience.py", f"""
        FAULT_SITES = {{
            "feeder_read": "reader raises once",{ghost}
        }}
    """)
    _write(tmp_path, "docs/robustness.md", """
        Fault plane. Sites: `feeder_read`. More prose.
    """)
    _write(tmp_path, "caffe_mpi_tpu/runtime.py", f"""
        def read(faults, i):
            faults.fire("feeder_read")
            {extra_call}
            return i
    """)
    return str(tmp_path)


def test_doc_drift_catches_undocumented_call_site(tmp_path):
    root = _mini_tree(tmp_path, 'faults.fire("surprise_site")')
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["doc-drift"], root=root)
    assert len(findings) == 1
    assert "surprise_site" in findings[0].message


def test_doc_drift_catches_dead_registry_entry(tmp_path):
    root = _mini_tree(tmp_path, ghost_entry=True)
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["doc-drift"], root=root)
    msgs = "\n".join(f.message for f in findings)
    assert "ghost_site" in msgs


def test_doc_drift_honors_waiver(tmp_path):
    root = _mini_tree(
        tmp_path,
        'faults.fire("surprise_site")  '
        "# lint: ok(doc-drift) — staged rollout, registered next PR")
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["doc-drift"], root=root)
    assert findings == []


def test_doc_drift_registry_waiver_agrees_across_entry_points(tmp_path):
    """A waived dead registry entry (staged rollout: call site lands
    next PR) must be clean via BOTH explicit paths and paths=[]."""
    root = _mini_tree(
        tmp_path,
        ghost_entry=True)
    # waive the ghost entry on its registry line
    reg = os.path.join(root, "caffe_mpi_tpu/utils/resilience.py")
    src = open(reg).read().replace(
        '"ghost_site": "never fired",',
        '"ghost_site": "never fired",  '
        "# lint: ok(doc-drift) — call site lands next PR")
    open(reg, "w").write(src)
    for paths in ([os.path.join(root, "caffe_mpi_tpu")], []):
        assert _run(paths, ["doc-drift"], root=root) == [], paths


def test_doc_drift_clean_tree_is_clean(tmp_path):
    root = _mini_tree(tmp_path)
    assert _run([os.path.join(root, "caffe_mpi_tpu")],
                ["doc-drift"], root=root) == []


# ---------------------------------------------------------------------------
# knob-drift (ISSUE 6): accepted-but-ignored perf knobs must fail

def _knob_tree(tmp_path, *, consume_all=True):
    """Minimal root satisfying all four legs for every registered knob;
    consume_all=False drops reduce_buckets' consumer (the seeded
    accept-and-ignore bug this pass exists to catch)."""
    from caffe_mpi_tpu.tools.lint.knob_drift import KNOBS
    fields = "\n".join(f"    {k}: int = 0" for k in KNOBS)
    _write(tmp_path, "caffe_mpi_tpu/proto/config.py",
           f"class SolverParameter:\n{fields}\n")
    _write(tmp_path, "caffe_mpi_tpu/tools/cli.py",
           "FLAGS = " + repr(list(KNOBS)) + "\n")
    _write(tmp_path, "docs/benchmarks.md",
           " ".join(f"`{k}`" for k in KNOBS) + "\n")
    reads = [k for k in KNOBS
             if consume_all or k != "reduce_buckets"]
    _write(tmp_path, "caffe_mpi_tpu/solver.py",
           "def f(sp):\n" + "".join(f"    sp.{k}\n" for k in reads)
           + "    return sp\n")
    return str(tmp_path)


def test_knob_drift_clean_tree_is_clean(tmp_path):
    root = _knob_tree(tmp_path)
    assert _run([os.path.join(root, "caffe_mpi_tpu")],
                ["knob-drift"], root=root) == []


def test_knob_drift_catches_accepted_but_ignored(tmp_path):
    root = _knob_tree(tmp_path, consume_all=False)
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["knob-drift"], root=root)
    assert len(findings) == 1
    assert "reduce_buckets" in findings[0].message
    assert "IGNORED" in findings[0].message


def test_knob_drift_honors_waiver(tmp_path):
    # the waiver sits on the field's line in the schema — the knob's
    # one stable anchor (fields here are emitted one per line, so the
    # trailing comment lands on the last field's line; waive ALL by
    # putting it above the class instead would hide real findings)
    from caffe_mpi_tpu.tools.lint.knob_drift import KNOBS
    root = _knob_tree(tmp_path, consume_all=False)
    cfg = os.path.join(root, "caffe_mpi_tpu/proto/config.py")
    src = open(cfg).read().replace(
        "    reduce_buckets: int = 0",
        "    reduce_buckets: int = 0  "
        "# lint: ok(knob-drift) — consumer lands next PR")
    open(cfg, "w").write(src)
    assert _run([os.path.join(root, "caffe_mpi_tpu")],
                ["knob-drift"], root=root) == []
    assert len(KNOBS) >= 5  # the ISSUE-6 knobs are registered


def test_knob_drift_write_is_not_consumption(tmp_path):
    # bench/CLI-style plumbing `sp.knob = v` is a Store-context
    # attribute — it must NOT satisfy the consumed leg, or deleting
    # every real reader would still ship lint-clean
    root = _knob_tree(tmp_path, consume_all=False)
    _write(tmp_path, "caffe_mpi_tpu/plumbing.py",
           "def f(sp, v):\n    sp.reduce_buckets = v\n")
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["knob-drift"], root=root)
    assert len(findings) == 1
    assert "reduce_buckets" in findings[0].message


def test_knob_drift_registry_and_docstrings_are_not_consumption(tmp_path):
    # the pass's own KNOBS tuple (anything under tools/lint/) and bare
    # docstring mentions must not neuter the consumed leg — only a
    # Load-context read or a call-argument string counts
    root = _knob_tree(tmp_path, consume_all=False)
    _write(tmp_path, "caffe_mpi_tpu/tools/lint/registry.py",
           "KNOBS = ('reduce_buckets',)\n")
    _write(tmp_path, "caffe_mpi_tpu/docmention.py",
           '"""module that merely talks about reduce_buckets"""\n')
    findings = _run([os.path.join(root, "caffe_mpi_tpu")],
                    ["knob-drift"], root=root)
    assert len(findings) == 1
    assert "reduce_buckets" in findings[0].message


def test_knob_drift_getattr_string_is_consumption(tmp_path):
    root = _knob_tree(tmp_path, consume_all=False)
    _write(tmp_path, "caffe_mpi_tpu/reader.py",
           "def f(sp):\n    return getattr(sp, 'reduce_buckets', 0)\n")
    assert _run([os.path.join(root, "caffe_mpi_tpu")],
                ["knob-drift"], root=root) == []


def test_doc_drift_waiver_honored_on_empty_path_selection(tmp_path):
    """The tier-1 wrapper (tests/test_doc_drift.py) runs the pass with
    paths=[]; waivers must hold there too, not only when the call-site
    file happens to be in the scanned selection — one enforcement
    path, two entry points."""
    root = _mini_tree(
        tmp_path,
        'faults.fire("surprise_site")  '
        "# lint: ok(doc-drift) — staged rollout, registered next PR")
    assert _run([], ["doc-drift"], root=root) == []
    # and the finding still fires without the waiver via paths=[]
    root2 = _mini_tree(tmp_path / "b", 'faults.fire("surprise_site")')
    findings = lint.run_lint(paths=[], select=["doc-drift"], root=root2)
    assert len(findings) == 1 and "surprise_site" in findings[0].message


# ---------------------------------------------------------------------------
# waiver grammar hard cases

def test_misspelled_waiver_still_fails(tmp_path):
    """A typo'd pass name neither suppresses the finding NOR passes
    silently: the finding survives and the bad waiver is itself
    reported."""
    p = _write(tmp_path, "typo.py", """
        def f(xs):
            out = []
            for x in xs:
                out.append(float(x))  # lint: ok(host-sink) — oops
            return out
    """)
    findings = _run([p], ["host-sync"])
    names = _names(findings)
    assert names == ["bad-waiver", "host-sync"], findings


def test_waiver_for_other_pass_does_not_suppress(tmp_path):
    p = _write(tmp_path, "wrongpass.py", """
        def f(xs):
            out = []
            for x in xs:
                out.append(float(x))  # lint: ok(gated-imports) — wrong pass
            return out
    """)
    findings = _run([p], ["host-sync"])
    assert _names(findings) == ["host-sync"]


def test_waiver_grammar_inside_a_string_does_not_suppress(tmp_path):
    """Text that merely QUOTES the waiver grammar (a message string, a
    docstring) must not register as a waiver — only real comment
    tokens count; otherwise a pass whose error message cites the
    grammar would self-waive."""
    p = _write(tmp_path, "quoted.py", """
        def f(losses):
            out = []
            for l in losses:
                out.append(("use # lint: ok(host-sync) to waive",
                            float(l)))
            return out
    """)
    findings = _run([p], ["host-sync"])
    assert [f.detail for f in findings] == ["float"]


def test_cli_non_py_file_is_usage_error_not_false_clean(tmp_path):
    doc = tmp_path / "notes.md"
    doc.write_text("# notes\n")
    assert lint.main([str(doc)]) == 2


def test_traced_control_flow_flags_lambda_body(tmp_path):
    p = _write(tmp_path, "lam.py", """
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: bool(jnp.any(x)))
    """)
    findings = _run([p], ["traced-control-flow"])
    assert [f.line for f in findings] == [5]


def test_traced_control_flow_lambda_finding_is_waivable(tmp_path):
    """A lambda body has no statements of its own; its findings anchor
    waivers on the enclosing statement, so the documented grammar
    works on jit-wrapped lambdas too."""
    p = _write(tmp_path, "lamw.py", """
        import jax
        import jax.numpy as jnp

        # lint: ok(traced-control-flow) — scalar pred, concrete at trace
        f = jax.jit(lambda x: bool(jnp.any(x)))
    """)
    assert _run([p], ["traced-control-flow"]) == []


def test_doc_drift_waiver_on_multiline_statement_span(tmp_path):
    """The waiver grammar promises the whole statement span; a
    trailing waiver on a multi-line fire(...) call must hold."""
    root = _mini_tree(
        tmp_path,
        'faults.fire("surprise_site",\n'
        '                        0)  '
        "# lint: ok(doc-drift) — staged rollout")
    assert _run([], ["doc-drift"], root=root) == []


def test_gated_imports_type_checking_else_branch_not_gated(tmp_path):
    p = _write(tmp_path, "tc.py", """
        from typing import TYPE_CHECKING

        if TYPE_CHECKING:
            import lmdb          # never runs: gated
        else:
            import flask         # ALWAYS runs: must be flagged

        def f():
            return 0
    """)
    findings = _run([p], ["gated-imports"])
    assert len(findings) == 1 and "flask" in findings[0].message


def test_trailing_waiver_does_not_leak_to_next_statement(tmp_path):
    """A trailing waiver belongs to ITS statement; the next statement's
    'line directly above' placement only counts for comment-only
    lines — otherwise one waiver silently suppresses two findings."""
    p = _write(tmp_path, "leak.py", """
        import numpy as np

        def f(ls, ms):
            out = []
            for l, m in zip(ls, ms):
                a = float(l)  # lint: ok(host-sync) — boundary
                b = np.asarray(m)
                out.append((a, b))
            return out
    """)
    findings = _run([p], ["host-sync"])
    assert [(f.line, f.detail) for f in findings] == [(8, "np.asarray")]


def test_gated_imports_handler_and_finally_not_gated(tmp_path):
    """Only the try BODY is protected by an ImportError handler; an
    unguarded gated import in the except/finally blocks raises at
    module-import time and must be flagged."""
    p = _write(tmp_path, "tryparts.py", """
        try:
            import lmdb                  # gated: fine
        except ImportError:
            import torch                 # NOT protected: flagged
        finally:
            import flask                 # NOT protected: flagged

        def f():
            return 0
    """)
    findings = _run([p], ["gated-imports"])
    assert sorted(f.line for f in findings) == [5, 7]


def test_traced_control_flow_bool_in_test_reports_once(tmp_path):
    """`if bool(jnp.any(x)):` is ONE defect — the branch flag consumes
    the test subtree so the nested bool() does not double-report."""
    p = _write(tmp_path, "dup.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            if bool(jnp.any(x)):
                x = x + 1
            return x
    """)
    findings = _run([p], ["traced-control-flow"])
    assert len(findings) == 1 and "`if`" in findings[0].message


def test_doc_drift_unrelated_trailing_waiver_does_not_leak(tmp_path):
    """A doc-drift waiver trailing the PREVIOUS statement must not
    suppress a call-site finding on the next line — and both entry
    points (explicit paths vs paths=[]) must agree."""
    root = _mini_tree(
        tmp_path,
        "x = 1  # lint: ok(doc-drift) — unrelated\n"
        '            faults.fire("surprise_site")')
    for paths in ([os.path.join(root, "caffe_mpi_tpu")], []):
        findings = _run(paths, ["doc-drift"], root=root)
        assert len(findings) == 1, (paths, findings)
        assert "surprise_site" in findings[0].message


def test_traced_control_flow_partial_jit_is_a_root(tmp_path):
    p = _write(tmp_path, "pjit.py", """
        from functools import partial
        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnums=0)
        def step(n, x):
            if jnp.sum(x) > 0:
                x = x + n
            return x
    """)
    findings = _run([p], ["traced-control-flow"])
    assert [f.line for f in findings] == [8]


def test_nested_waiver_does_not_suppress_header_finding(tmp_path):
    """A finding anchored to a compound statement (if/while header)
    spans only the HEADER — a waiver on some statement nested in the
    body must not silently suppress it."""
    p = _write(tmp_path, "hdr.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, idx):
            if jnp.sum(x) > 0:
                # lint: ok(traced-control-flow) — static index
                y = int(jnp.argmax(x))
                x = x + y
            return x
    """)
    findings = _run([p], ["traced-control-flow"])
    assert len(findings) == 1 and "`if`" in findings[0].message


def test_doc_drift_sees_wrapped_call_sites(tmp_path):
    """`fire(\\n \"site\")` wrapped across lines must still register as
    a call site (whole-text scan, as the pre-framework test did)."""
    root = _mini_tree(
        tmp_path,
        'faults.fire(\n                "surprise_site")')
    findings = _run([], ["doc-drift"], root=root)
    assert len(findings) == 1
    assert "surprise_site" in findings[0].message


def test_multi_pass_waiver(tmp_path):
    p = _write(tmp_path, "multi.py", """
        def f(xs):
            out = []
            for x in xs:
                # lint: ok(host-sync, traced-control-flow) — host floats
                out.append(float(x))
            return out
    """)
    assert _run([p], ["host-sync", "traced-control-flow"]) == []


# ---------------------------------------------------------------------------
# blocking-under-lock (ISSUE 13): the PR 7 / PR 11 regression shapes

_PR7_SET_RESULT_UNDER_REC_LOCK = """
    import threading

    class Batcher:
        def __init__(self):
            self._rec_lock = threading.Lock()
            self._records = []

        def harvest(self, group, scores):
            with self._rec_lock:
                self._records.append(len(group))
                for i, r in enumerate(group):
                    r.future.set_result(scores[i])
"""

_PR11_UPLOAD_UNDER_UPLOAD_LOCK = """
    import threading

    class InferenceModel:
        def __init__(self):
            self._upload_lock = threading.Lock()
            self._resident = None

        def ensure_resident(self, host):
            import jax
            with self._upload_lock:
                if self._resident is None:
                    self._resident = jax.device_put(host)
                return self._resident
"""


def test_blocking_catches_pr7_set_result_under_rec_lock(tmp_path):
    """The PR 7 second-round deadlock shape: a Future resolved under
    the non-reentrant records lock (done-callbacks run synchronously
    in the resolving thread)."""
    p = _write(tmp_path, "b.py", _PR7_SET_RESULT_UNDER_REC_LOCK)
    findings = _run([p], ["blocking-under-lock"], root=str(tmp_path))
    assert len(findings) == 1
    assert "Future.set_result" in findings[0].message
    assert "_rec_lock" in findings[0].message


def test_blocking_catches_pr11_upload_under_upload_lock(tmp_path):
    """The PR 11 shape: a seconds-long device upload inside a held
    lock span."""
    p = _write(tmp_path, "m.py", _PR11_UPLOAD_UNDER_UPLOAD_LOCK)
    findings = _run([p], ["blocking-under-lock"], root=str(tmp_path))
    assert len(findings) == 1
    assert "jax.device_put" in findings[0].message
    assert "_upload_lock" in findings[0].message


def test_blocking_honors_waiver(tmp_path):
    p = _write(tmp_path, "w.py", """
        import threading

        class InferenceModel:
            def __init__(self):
                self._upload_lock = threading.Lock()

            def ensure_resident(self, host):
                import jax
                with self._upload_lock:
                    # lint: ok(blocking-under-lock) — upload serialization
                    # is this lock's purpose; no other lock is held here
                    return jax.device_put(host)
    """)
    assert _run([p], ["blocking-under-lock"], root=str(tmp_path)) == []


def test_blocking_flags_unbounded_waits_but_not_condition_wait(tmp_path):
    """queue.get()/join()/result() with no timeout block forever under
    a lock; a Condition's own .wait() under its lock is the sanctioned
    pattern (it RELEASES the lock) and must not be flagged."""
    p = _write(tmp_path, "u.py", """
        import threading

        class Pump:
            def __init__(self):
                self._cv = threading.Condition()
                self._q = None
                self._t = None

            def run_ok(self):
                with self._cv:
                    while self._q is None:
                        self._cv.wait()          # sanctioned

            def run_bad(self, fut):
                with self._cv:
                    item = self._q.get()         # unbounded
                    self._t.join()               # unbounded
                    return fut.result(), item    # unbounded
    """)
    findings = _run([p], ["blocking-under-lock"], root=str(tmp_path))
    kinds = sorted(f.message.split(" inside")[0] for f in findings)
    assert kinds == [".get() without timeout", ".join() without timeout",
                     ".result() without timeout"]


def test_blocking_outside_lock_is_clean(tmp_path):
    """The fixed shapes — snapshot under the lock, resolve outside —
    must be clean (the diff that fixed PR 7 has to lint clean)."""
    p = _write(tmp_path, "ok.py", """
        import threading

        class Batcher:
            def __init__(self):
                self._rec_lock = threading.Lock()
                self._records = []

            def harvest(self, group, scores):
                with self._rec_lock:
                    self._records.append(len(group))
                for i, r in enumerate(group):
                    r.future.set_result(scores[i])
    """)
    assert _run([p], ["blocking-under-lock"], root=str(tmp_path)) == []


# ---------------------------------------------------------------------------
# lock-order (ISSUE 13): nesting vs the declared LOCK_ORDER

_TWO_LOCK_CLASSES = """
    import threading

    class InferenceModel:
        def __init__(self):
            self._upload_lock = threading.Lock()

    class ServingEngine:
        def __init__(self):
            self._lock = threading.Lock()

        def swap(self, model):
            with model._upload_lock:
                with self._lock:
                    pass
"""


def _lock_registry(tmp_path, body):
    return _write(tmp_path, "caffe_mpi_tpu/serving/locks.py", body)


def test_lock_order_undeclared_nesting_is_a_finding(tmp_path):
    p = _write(tmp_path, "eng.py", _TWO_LOCK_CLASSES)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    assert len(findings) == 1
    assert "undeclared lock nesting" in findings[0].message
    assert "InferenceModel._upload_lock" in findings[0].message


def test_lock_order_declared_nesting_is_clean(tmp_path):
    _lock_registry(tmp_path, """
        LOCK_ORDER = (
            ("InferenceModel._upload_lock", "ServingEngine._lock"),
        )
    """)
    p = _write(tmp_path, "eng.py", _TWO_LOCK_CLASSES)
    assert _run([p], ["lock-order"], root=str(tmp_path)) == []


def test_lock_order_catches_inverted_upload_engine_nesting(tmp_path):
    """The acceptance shape: LOCK_ORDER declares _upload_lock ->
    engine._lock; code that nests engine._lock -> _upload_lock is the
    PR 11 deadlock inversion and must fail LOUDLY."""
    _lock_registry(tmp_path, """
        LOCK_ORDER = (
            ("InferenceModel._upload_lock", "ServingEngine._lock"),
        )
    """)
    p = _write(tmp_path, "eng.py", """
        import threading

        class InferenceModel:
            def __init__(self):
                self._upload_lock = threading.Lock()

        class ServingEngine:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_swap(self, model):
                with self._lock:
                    with model._upload_lock:
                        pass
    """)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    assert len(findings) == 1
    assert "INVERTED" in findings[0].message


def test_lock_order_sees_nesting_through_resolvable_calls(tmp_path):
    """Holding lock A while CALLING a method that acquires lock B is
    the same nesting as a syntactic with-in-with — the PR 7 dispatcher
    shape (engine.model under the batcher's condition variable)."""
    p = _write(tmp_path, "call.py", """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()

            def model(self, name):
                with self._lock:
                    return name

        class Batcher:
            def __init__(self):
                self._cv = threading.Condition()
                self._engine = Engine()

            def dispatch(self, name):
                with self._cv:
                    return self._engine.model(name)
    """)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    assert len(findings) == 1
    assert "Batcher._cv" in findings[0].message
    assert "Engine._lock" in findings[0].message
    assert "call to Engine.model" in findings[0].message


def test_lock_order_reacquire_nonreentrant_flagged_rlock_clean(tmp_path):
    p = _write(tmp_path, "re.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._rlock = threading.RLock()

            def bad(self):
                with self._lock:
                    with self._lock:
                        pass

            def fine(self):
                with self._rlock:
                    with self._rlock:
                        pass
    """)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    assert len(findings) == 1
    assert "self-deadlock" in findings[0].message


def test_lock_order_registry_drift_unknown_lock_fails(tmp_path):
    """A LOCK_ORDER entry naming a lock that no longer exists in the
    tree is itself a finding — the registry cannot outlive the code
    (the acceptance's seeded-mismatch case)."""
    _lock_registry(tmp_path, """
        LOCK_ORDER = (
            ("Ghost._lock", "AlsoGhost._lock"),
        )
    """)
    p = _write(tmp_path, "code.py", """
        def f():
            return 1
    """)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    msgs = "\\n".join(f.message for f in findings)
    assert "unknown lock 'Ghost._lock'" in msgs
    assert "unknown lock 'AlsoGhost._lock'" in msgs


def test_lock_order_registry_cycle_fails(tmp_path):
    _lock_registry(tmp_path, """
        LOCK_ORDER = (
            ("A._lock", "B._lock"),
            ("B._lock", "A._lock"),
        )
    """)
    p = _write(tmp_path, "code.py", """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

        class B:
            def __init__(self):
                self._lock = threading.Lock()
    """)
    findings = _run([p], ["lock-order"], root=str(tmp_path))
    assert any("cycle" in f.message for f in findings)


def test_lock_order_honors_waiver(tmp_path):
    p = _write(tmp_path, "w.py", """
        import threading

        class A:
            def __init__(self):
                self._outer = threading.Lock()
                self._inner = threading.Lock()

            def f(self):
                with self._outer:
                    # lint: ok(lock-order) — fixture: deliberate nesting
                    with self._inner:
                        pass
    """)
    assert _run([p], ["lock-order"], root=str(tmp_path)) == []


def test_shipped_lock_order_registry_matches_tree():
    """The real registry drift-holds against the real tree: every
    LOCK_ORDER node and ATTR_TYPES entry must resolve (a rename that
    misses serving/locks.py fails here and in the CLI)."""
    findings = _run([], ["lock-order"], root=_ROOT)
    assert findings == [], [f.format(_ROOT) for f in findings]


# ---------------------------------------------------------------------------
# thread-shared-mutation (ISSUE 13)

def test_thread_shared_mutation_catches_seeded_race(tmp_path):
    p = _write(tmp_path, "race.py", """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self._count += 1

            def bump(self):
                with self._lock:
                    self._count += 1
    """)
    findings = _run([p], ["thread-shared-mutation"], root=str(tmp_path))
    assert len(findings) == 1
    assert "self._count" in findings[0].message
    assert "Worker._run" in findings[0].message


def test_thread_shared_mutation_both_locked_is_clean(tmp_path):
    p = _write(tmp_path, "ok.py", """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self._count += 1

            def bump(self):
                with self._lock:
                    self._count += 1
    """)
    assert _run([p], ["thread-shared-mutation"],
                root=str(tmp_path)) == []


def test_thread_shared_mutation_honors_waiver_and_init_exempt(tmp_path):
    """__init__ mutations don't count (no thread exists yet), and the
    waiver-with-reason contract holds — PER SITE: every unlocked racy
    mutation site is its own finding, so each carries its own waiver
    (one waived anchor must not silence a race added elsewhere)."""
    p = _write(tmp_path, "w.py", """
        import threading

        class Worker:
            def __init__(self):
                self._state = 0     # pre-thread: exempt
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                # lint: ok(thread-shared-mutation) — reset() is only
                # called after join() in this fixture's lifecycle
                self._state = 1

            def reset(self):
                # lint: ok(thread-shared-mutation) — only called after
                # join(), same lifecycle contract as _run above
                self._state = 0
    """)
    assert _run([p], ["thread-shared-mutation"],
                root=str(tmp_path)) == []


def test_thread_shared_mutation_reports_every_unlocked_site(tmp_path):
    """A waiver on one racy site must not silence a DIFFERENT unlocked
    site of the same attribute — each gets its own finding."""
    p = _write(tmp_path, "two.py", """
        import threading

        class Worker:
            def __init__(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                # lint: ok(thread-shared-mutation) — fixture: waived site
                self._state = 1

            def reset(self):
                self._state = 0
    """)
    findings = _run([p], ["thread-shared-mutation"], root=str(tmp_path))
    assert len(findings) == 1
    assert "reset" in findings[0].message


def test_thread_shared_mutation_pool_submit_is_an_entry(tmp_path):
    """A ThreadPoolExecutor.submit callee is a thread body too (the
    feeder's pool workers)."""
    p = _write(tmp_path, "pool.py", """
        from concurrent.futures import ThreadPoolExecutor

        class Feeder:
            def __init__(self):
                self.pool = ThreadPoolExecutor(2)
                self._mode = None

            def schedule(self, it):
                return self.pool.submit(self._build, it)

            def _build(self, it):
                self._mode = "fused"
                return it

            def retune(self):
                self._mode = "classic"
    """)
    findings = _run([p], ["thread-shared-mutation"], root=str(tmp_path))
    # per-site reporting: the pool-worker write AND the public write
    # are each their own finding
    assert len(findings) == 2
    assert all("self._mode" in f.message for f in findings)


# ---------------------------------------------------------------------------
# exit-code drift (ISSUE 13 satellite, folded into doc-drift)

def _exit_tree(tmp_path, *, doc_code=86, call="os._exit(EXIT_WATCHDOG)"):
    _write(tmp_path, "caffe_mpi_tpu/utils/resilience.py", f"""
        import os
        EXIT_WATCHDOG = 86
        EXIT_FAULT = 87
        EXIT_CLUSTER = EXIT_FAULT

        def die():
            {call}
    """)
    _write(tmp_path, "docs/robustness.md", f"""
        Exit codes:

        | code | name | meaning |
        |---|---|---|
        | **{doc_code}** | `EXIT_WATCHDOG` | watchdog trip |
        | **87** | `EXIT_CLUSTER` / `EXIT_FAULT` | cluster loss |
    """)
    return str(tmp_path)


def test_exit_drift_clean_tree_is_clean(tmp_path):
    root = _exit_tree(tmp_path)
    assert _run([], ["doc-drift"], root=root) == []


def test_exit_drift_docs_code_mismatch_fails(tmp_path):
    """The PR 11 rot class: the docs table claiming a different number
    than the registry sends operators hunting a death that never
    happened."""
    root = _exit_tree(tmp_path, doc_code=96)
    findings = _run([], ["doc-drift"], root=root)
    msgs = "\\n".join(f.message for f in findings)
    assert "EXIT_WATCHDOG" in msgs and "96" in msgs


def test_exit_drift_bare_literal_exit_fails(tmp_path):
    root = _exit_tree(tmp_path, call="os._exit(86)")
    findings = _run([], ["doc-drift"], root=root)
    assert len(findings) == 1
    assert "bare literal exit 86" in findings[0].message
    assert "EXIT_WATCHDOG" in findings[0].message


def test_exit_drift_unregistered_symbol_fails(tmp_path):
    root = _exit_tree(tmp_path, call="os._exit(EXIT_BOGUS)")
    findings = _run([], ["doc-drift"], root=root)
    assert len(findings) == 1
    assert "EXIT_BOGUS" in findings[0].message


def test_exit_drift_missing_docs_entry_fails(tmp_path):
    root = _exit_tree(tmp_path)
    docs = os.path.join(root, "docs/robustness.md")
    src = open(docs).read().replace(
        "| **87** | `EXIT_CLUSTER` / `EXIT_FAULT` | cluster loss |", "")
    open(docs, "w").write(src)
    findings = _run([], ["doc-drift"], root=root)
    msgs = "\\n".join(f.message for f in findings)
    assert "EXIT_FAULT" in msgs and "EXIT_CLUSTER" in msgs


def test_exit_drift_bare_literal_waivable(tmp_path):
    root = _exit_tree(
        tmp_path,
        call="os._exit(86)  # lint: ok(doc-drift) — pre-registry shim")
    assert _run([], ["doc-drift"], root=root) == []


def test_exit_drift_waiver_in_comment_block_above_binds(tmp_path):
    """The documented contiguous-comment-block binding holds for the
    self-applied exit-call waivers too — a multi-line reason must not
    detach the waiver from its statement."""
    _write(tmp_path, "caffe_mpi_tpu/utils/resilience.py", """
        import os
        EXIT_WATCHDOG = 86

        def die():
            # lint: ok(doc-drift) — pre-registry shim kept for one
            # release so old supervisors keep matching on the number
            os._exit(86)
    """)
    _write(tmp_path, "docs/robustness.md", """
        | **86** | `EXIT_WATCHDOG` | watchdog trip |
    """)
    assert _run([], ["doc-drift"], root=str(tmp_path)) == []


# ---------------------------------------------------------------------------
# stale-waiver detection (ISSUE 13 satellite)

def test_stale_waiver_reported_when_pass_no_longer_fires(tmp_path):
    p = _write(tmp_path, "stale.py", """
        import numpy as np

        def f(x):
            # not in a loop: host-sync has nothing to say here
            return float(x)  # lint: ok(host-sync) — display boundary
    """)
    findings = lint.run_lint([p], select=["host-sync"],
                             root=str(tmp_path), stale=True)
    assert len(findings) == 1
    assert findings[0].pass_name == "stale-waiver"
    assert "host-sync" in findings[0].message


def test_stale_waiver_not_reported_for_honored_waiver(tmp_path):
    p = _write(tmp_path, "honored.py", """
        def f(xs):
            out = []
            for x in xs:
                out.append(float(x))  # lint: ok(host-sync) — fixture
            return out
    """)
    assert lint.run_lint([p], select=["host-sync"],
                         root=str(tmp_path), stale=True) == []


def test_stale_waiver_off_by_default_in_library_api(tmp_path):
    p = _write(tmp_path, "stale.py", """
        def f(x):
            return float(x)  # lint: ok(host-sync) — fixture
    """)
    assert lint.run_lint([p], select=["host-sync"],
                         root=str(tmp_path)) == []


def test_stale_waiver_only_judges_selected_passes(tmp_path):
    """A --select run must not call waivers for UNSELECTED passes
    stale — those passes never got the chance to fire."""
    p = _write(tmp_path, "other.py", """
        def f(x):
            return float(x)  # lint: ok(host-sync) — fixture
    """)
    assert lint.run_lint([p], select=["gated-imports"],
                         root=str(tmp_path), stale=True) == []


def test_stale_waiver_multiline_comment_block_binds_to_statement(tmp_path):
    """A waiver anywhere in the contiguous comment block directly above
    the statement is honored (multi-line reasons are encouraged, not
    punished)."""
    p = _write(tmp_path, "block.py", """
        def f(xs):
            out = []
            for x in xs:
                # lint: ok(host-sync) — the reason here is long enough
                # to need a second comment line, which must not detach
                # the waiver from its statement
                out.append(float(x))
            return out
    """)
    assert lint.run_lint([p], select=["host-sync"],
                         root=str(tmp_path), stale=True) == []


# ---------------------------------------------------------------------------
# --changed CLI mode (ISSUE 13 satellite)

def test_changed_mode_typod_ref_is_usage_error():
    """A typo'd git ref must exit 2 (usage error), NEVER a false-clean
    exit 0 with zero files scanned."""
    assert lint.main(["--changed", "no-such-ref-xyz"]) == 2


def test_changed_mode_valid_ref_is_not_a_usage_error():
    assert lint.main(["--changed", "HEAD", "--no-stale"]) != 2


def test_changed_mode_explicit_paths_still_lint(tmp_path):
    bad = _write(tmp_path, "bad.py", """
        def f(xs):
            return [float(x) for x in xs]
    """)
    assert lint.main(["--changed", "HEAD", "--select", "host-sync",
                      "--no-stale", bad]) == 1


def test_changed_mode_skips_files_outside_the_scanned_tree(monkeypatch):
    """tests/ and examples/ are deliberately outside the lint contract
    (torch-oracle host syncs etc.) — a commit touching only such files
    must not fail the pre-commit run on code the full scan exempts."""
    import subprocess

    real_run = subprocess.run

    def fake_run(cmd, **kw):
        if cmd[:3] == ["git", "diff", "--name-only"]:
            class R:
                returncode = 0
                stdout = "tests/test_multistep.py\nexamples/mnist/run.py\n"
                stderr = ""
            return R()
        return real_run(cmd, **kw)

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert lint.main(["--changed", "HEAD", "--select", "host-sync",
                      "--no-stale"]) == 0


def test_changed_mode_wedged_git_is_usage_error(monkeypatch):
    """A git that never answers (dead NFS, lock contention) must turn
    into exit 2, not hang the pre-commit hook forever — the diff query
    itself obeys deadline discipline."""
    import subprocess

    real_run = subprocess.run

    def fake_run(cmd, **kw):
        if cmd[:3] == ["git", "diff", "--name-only"]:
            raise subprocess.TimeoutExpired(cmd, kw.get("timeout", 60))
        return real_run(cmd, **kw)

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert lint.main(["--changed", "HEAD", "--no-stale"]) == 2


def test_precommit_script_propagates_typod_ref_exit_2():
    """tools/precommit.sh (ISSUE 20 satellite) rides tpulint's
    --changed contract: a typo'd ref exits 2 through the whole script
    (set -e stops before pytest ever runs) — never a false-clean 0."""
    r = subprocess.run(
        ["sh", os.path.join(_ROOT, "tools", "precommit.sh"),
         "no-such-ref-xyz"],
        capture_output=True, text=True, timeout=120, cwd=_ROOT)
    assert r.returncode == 2, (r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# failure-path family (ISSUE 20): future-resolution

def test_future_resolution_catches_pr7_create_then_raise(tmp_path):
    """The PR 7 regression shape: Batcher.submit created the Future
    BEFORE the admission checks, so a shed/closed raise left the caller
    holding a reference nobody would ever resolve."""
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        from concurrent.futures import Future

        class Batcher:
            def submit(self, item, closed, backlog, limit):
                fut = Future()
                if closed:
                    raise RuntimeError("engine closed")
                if backlog > limit:
                    raise RuntimeError("shed")
                self._queue.append((item, fut))
                return fut
    """)
    findings = _run([p], ["future-resolution"], root=str(tmp_path))
    # one finding per stranded future (the first raise edge reports
    # it; linear flow then treats it as judged)
    assert len(findings) == 1
    assert "PR 7" in findings[0].message
    assert "'fut'" in findings[0].message


def test_future_resolution_clean_when_created_after_admission(tmp_path):
    """The shipped fix for the PR 7 shape: run every raise-path check
    first, create the Future only once admission is certain."""
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        from concurrent.futures import Future

        class Batcher:
            def submit(self, item, closed, backlog, limit):
                if closed:
                    raise RuntimeError("engine closed")
                if backlog > limit:
                    raise RuntimeError("shed")
                fut = Future()
                self._queue.append((item, fut))
                return fut
    """)
    assert _run([p], ["future-resolution"], root=str(tmp_path)) == []


def test_future_resolution_resolved_on_error_path_is_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        from concurrent.futures import Future

        class Batcher:
            def submit(self, item):
                fut = Future()
                try:
                    self._enqueue(item, fut)
                except Exception as e:
                    fut.set_exception(e)
                    raise
                return fut
    """)
    assert _run([p], ["future-resolution"], root=str(tmp_path)) == []


def test_future_resolution_out_of_scope_path_is_clean(tmp_path):
    """The pass is scoped to serving/ + solver/ — a data-pipeline
    helper juggling futures is not on the request path."""
    p = _write(tmp_path, "caffe_mpi_tpu/data/feeder.py", """
        from concurrent.futures import Future

        def stage(closed):
            fut = Future()
            if closed:
                raise RuntimeError("closed")
            return fut
    """)
    assert _run([p], ["future-resolution"], root=str(tmp_path)) == []


def test_future_resolution_honors_waiver(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        from concurrent.futures import Future

        class Batcher:
            def submit(self, item, closed):
                fut = Future()
                if closed:
                    # lint: ok(future-resolution) — fixture: ownership
                    # is provably elsewhere in this contrived shape
                    raise RuntimeError("closed")
                self._queue.append(fut)
                return fut
    """)
    assert _run([p], ["future-resolution"], root=str(tmp_path)) == []


def test_future_resolution_stale_waiver_reported(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        from concurrent.futures import Future

        class Batcher:
            def submit(self, item):
                # lint: ok(future-resolution) — fixture: nothing fires
                fut = Future()
                self._queue.append(fut)
                return fut
    """)
    findings = lint.run_lint([p], select=["future-resolution"],
                             root=str(tmp_path), stale=True)
    assert len(findings) == 1
    assert findings[0].pass_name == "stale-waiver"
    assert "future-resolution" in findings[0].message


# ---------------------------------------------------------------------------
# failure-path family (ISSUE 20): typed-failure

def test_typed_failure_catches_log_and_continue(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/solver/loop.py", """
        import logging
        log = logging.getLogger(__name__)

        def step(net):
            try:
                net.dispatch()
            except Exception:
                log.warning("dispatch failed")
    """)
    findings = _run([p], ["typed-failure"], root=str(tmp_path))
    assert len(findings) == 1
    assert "swallows the failure UNTYPED" in findings[0].message


def test_typed_failure_bare_except_pass_fails(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/router.py", """
        def route(req, engine):
            try:
                return engine.submit(req)
            except:
                pass
    """)
    findings = _run([p], ["typed-failure"], root=str(tmp_path))
    assert len(findings) == 1
    assert "bare except" in findings[0].message


def test_typed_failure_reraise_and_journal_are_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/router.py", """
        def route(req, engine):
            try:
                return engine.submit(req)
            except Exception as e:
                engine.journal("route_failed", error=str(e))

        def close(engine):
            try:
                engine.drain()
            except Exception:
                raise
    """)
    assert _run([p], ["typed-failure"], root=str(tmp_path)) == []


def test_typed_failure_resolving_future_with_error_is_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/router.py", """
        def route(req, fut, engine):
            try:
                fut.set_result(engine.submit(req))
            except Exception as e:
                fut.set_exception(e)
    """)
    assert _run([p], ["typed-failure"], root=str(tmp_path)) == []


def test_typed_failure_out_of_scope_path_is_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/data/reader.py", """
        def read(db):
            try:
                return db.get()
            except Exception:
                return None
    """)
    assert _run([p], ["typed-failure"], root=str(tmp_path)) == []


def test_typed_failure_honors_waiver(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/parallel/mesh_fx.py", """
        def teardown(svc):
            try:
                svc.shutdown()
            # lint: ok(typed-failure) — fixture: already-down IS the
            # goal state of a teardown
            except Exception:
                pass
    """)
    assert _run([p], ["typed-failure"], root=str(tmp_path)) == []


def test_typed_failure_stale_waiver_reported(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/parallel/mesh_fx.py", """
        def teardown(svc):
            try:
                svc.shutdown()
            # lint: ok(typed-failure) — fixture: nothing fires here
            except Exception:
                raise
    """)
    findings = lint.run_lint([p], select=["typed-failure"],
                             root=str(tmp_path), stale=True)
    assert len(findings) == 1
    assert findings[0].pass_name == "stale-waiver"
    assert "typed-failure" in findings[0].message


# ---------------------------------------------------------------------------
# failure-path family (ISSUE 20): thread-crash

def test_thread_crash_catches_unguarded_target(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/monitor.py", """
        import threading

        class Monitor:
            def start(self):
                threading.Thread(target=self._loop,
                                 daemon=True).start()

            def _loop(self):
                while True:
                    self.poll()
    """)
    findings = _run([p], ["thread-crash"], root=str(tmp_path))
    assert len(findings) == 1
    assert "kills the worker SILENTLY" in findings[0].message
    assert "_loop" in findings[0].message


def test_thread_crash_guarded_target_is_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/monitor.py", """
        import threading

        class Monitor:
            def start(self):
                threading.Thread(target=self._loop,
                                 daemon=True).start()

            def _loop(self):
                try:
                    while True:
                        self.poll()
                except Exception as e:
                    self.journal("monitor_crash", error=str(e))
    """)
    assert _run([p], ["thread-crash"], root=str(tmp_path)) == []


def test_thread_crash_catches_pr11_dispatcher_via_local_tuple(tmp_path):
    """The PR 11 regression shape: the dispatcher worker loop reaches
    Thread() through a local (name, target) tuple, so a target= match
    alone misses it — the escaping worker-loop reference must flag."""
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        import threading

        class Batcher:
            def ensure_threads(self):
                specs = [("dispatch", self._dispatch_loop),
                         ("harvest", self._harvest_loop)]
                for name, target in specs:
                    t = threading.Thread(target=target, name=name,
                                         daemon=True)
                    t.start()

            def _dispatch_loop(self):
                while not self._closed:
                    self._dispatch_once()

            def _harvest_loop(self):
                try:
                    while not self._closed:
                        self._harvest_once()
                except Exception as e:
                    self._journal("harvest_crash", error=str(e))
    """)
    findings = _run([p], ["thread-crash"], root=str(tmp_path))
    assert len(findings) == 1
    assert "_dispatch_loop" in findings[0].message


def test_thread_crash_discarded_pool_submit_flagged_kept_clean(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/workers.py", """
        def fan_out(pool, records):
            for r in records:
                pool.submit(_render, r)

        def fan_out_kept(pool, records):
            futs = [pool.submit(_render, r) for r in records]
            return futs

        def _render(r):
            return r.decode()
    """)
    findings = _run([p], ["thread-crash"], root=str(tmp_path))
    assert len(findings) == 1
    assert "discards its future" in findings[0].message


def test_thread_crash_honors_waiver(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/beat.py", """
        import threading

        class Beat:
            def start(self):
                threading.Thread(target=self._loop,
                                 daemon=True).start()

            # lint: ok(thread-crash) — fixture: a dead beat IS the
            # failure signal; the supervisor mourns the silence
            def _loop(self):
                while True:
                    self.publish()
    """)
    assert _run([p], ["thread-crash"], root=str(tmp_path)) == []


def test_thread_crash_stale_waiver_reported(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/beat.py", """
        import threading

        class Beat:
            def start(self):
                threading.Thread(target=self._loop,
                                 daemon=True).start()

            # lint: ok(thread-crash) — fixture: nothing fires here
            def _loop(self):
                try:
                    while True:
                        self.publish()
                except Exception as e:
                    self.journal("beat_crash", error=str(e))
    """)
    findings = lint.run_lint([p], select=["thread-crash"],
                             root=str(tmp_path), stale=True)
    assert len(findings) == 1
    assert findings[0].pass_name == "stale-waiver"
    assert "thread-crash" in findings[0].message


# ---------------------------------------------------------------------------
# failure-path family (ISSUE 20): deadline-discipline

def test_deadline_catches_unbounded_subprocess_and_result(tmp_path):
    p = _write(tmp_path, "tools/probe.py", """
        import subprocess

        def probe(cmd, fut):
            subprocess.run(cmd, capture_output=True)
            return fut.result()
    """)
    findings = _run([p], ["deadline-discipline"], root=str(tmp_path))
    assert len(findings) == 2
    msgs = " ".join(f.message for f in findings)
    assert "hang no" in msgs


def test_deadline_bounded_calls_are_clean(tmp_path):
    p = _write(tmp_path, "tools/probe.py", """
        import subprocess

        def probe(cmd, fut):
            subprocess.run(cmd, capture_output=True, timeout=60)
            return fut.result(timeout=30)
    """)
    assert _run([p], ["deadline-discipline"], root=str(tmp_path)) == []


def test_deadline_module_level_call_is_covered(tmp_path):
    """Smoke scripts run subprocess at module/__main__ level, outside
    any function the model walks — those statements must not escape."""
    p = _write(tmp_path, "tools/smoke.py", """
        import subprocess

        r = subprocess.run(["python", "-c", "pass"],
                           capture_output=True)
    """)
    findings = _run([p], ["deadline-discipline"], root=str(tmp_path))
    assert len(findings) == 1
    assert "subprocess.run" in findings[0].message


def test_deadline_out_of_scope_path_is_clean(tmp_path):
    """data/ is host-side io with no device adjacency — unbounded
    waits there are blocking-under-lock's business only when a lock
    is held."""
    p = _write(tmp_path, "caffe_mpi_tpu/data/prefetch.py", """
        def drain(q):
            return q.get()
    """)
    assert _run([p], ["deadline-discipline"], root=str(tmp_path)) == []


def test_deadline_honors_waiver(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        def harvest(q):
            while True:
                # lint: ok(deadline-discipline) — fixture: sentinel-
                # woken idle park; close() enqueues None
                item = q.get()
                if item is None:
                    return
    """)
    assert _run([p], ["deadline-discipline"], root=str(tmp_path)) == []


def test_deadline_stale_waiver_reported(tmp_path):
    p = _write(tmp_path, "caffe_mpi_tpu/serving/batching.py", """
        def harvest(q):
            while True:
                # lint: ok(deadline-discipline) — fixture: stale
                item = q.get(timeout=5.0)
                if item is None:
                    return
    """)
    findings = lint.run_lint([p], select=["deadline-discipline"],
                             root=str(tmp_path), stale=True)
    assert len(findings) == 1
    assert findings[0].pass_name == "stale-waiver"
    assert "deadline-discipline" in findings[0].message


# ---------------------------------------------------------------------------
# --profile (ISSUE 20 satellite)

_INTERPROCEDURAL = ("lock-order", "blocking-under-lock",
                    "thread-shared-mutation", "future-resolution",
                    "typed-failure", "thread-crash",
                    "deadline-discipline")


def test_profile_one_shared_model_build(tmp_path):
    """All seven interprocedural passes must share ONE tree_model
    build per run — per-pass rebuilds are how the 5 s budget dies."""
    _write(tmp_path, "caffe_mpi_tpu/serving/engine_fx.py", """
        import threading

        class E:
            def start(self):
                threading.Thread(target=self._loop,
                                 daemon=True).start()

            def _loop(self):
                try:
                    while True:
                        self.step()
                except Exception as e:
                    self.journal("crash", error=str(e))
    """)
    profile = {}
    lint.run_lint(paths=None, select=list(_INTERPROCEDURAL),
                  root=str(tmp_path), profile=profile)
    assert profile["model_builds"] == 1, profile
    for name in _INTERPROCEDURAL:
        assert name in profile["passes"], profile


def test_profile_text_table_on_stderr(tmp_path, capsys):
    _write(tmp_path, "ok.py", """
        '''Replaces nothing.py:1 — fixture.'''
    """)
    rc = lint.main(["--profile", "--no-stale", "--select", "host-sync",
                    str(tmp_path / "ok.py")])
    assert rc == 0
    err = capsys.readouterr().err
    assert "lint --profile:" in err
    assert "host-sync" in err
    assert "shared model build(s)" in err


def test_profile_json_envelope_and_bare_json_unchanged(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", """
        def f(xs):
            return [float(x) for x in xs]
    """)
    rc = lint.main(["--profile", "--json", "--no-stale",
                    "--select", "host-sync", bad])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    # --profile + --json opts into the envelope...
    assert set(out) == {"findings", "profile"}
    assert out["findings"][0]["pass"] == "host-sync"
    assert "passes" in out["profile"]
    assert "model_builds" in out["profile"]
    # ...while plain --json keeps the bare-array contract
    rc = lint.main(["--json", "--no-stale", "--select", "host-sync", bad])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert isinstance(out, list) and out[0]["pass"] == "host-sync"
