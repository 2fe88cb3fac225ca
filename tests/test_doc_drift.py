"""Doc-drift tripwires — THIN WRAPPER over the lint framework's
doc-drift pass (ISSUE 5: one enforcement path, two entry points; the
substance lives in caffe_mpi_tpu/tools/lint/doc_drift.py and is also
reachable as `python -m caffe_mpi_tpu.tools.lint --select doc-drift`).

Held equal by the pass: the `FAULT_SITES` registry in
utils/resilience.py, the `Sites:` list in docs/robustness.md, and the
literal site names at FAULTS call sites. Pure text/AST — no jax, no
device work; tier-1 cheap.
"""

import os
import re

import pytest

from caffe_mpi_tpu.tools import lint

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFaultSiteDrift:
    def test_registry_docs_and_call_sites_agree(self):
        """The doc-drift pass holds registry == docs == call sites (and
        every registry entry described); any drift is a finding."""
        findings = lint.run_lint(paths=[], select=["doc-drift"],
                                 root=_ROOT)
        assert findings == [], "\n".join(f.format(_ROOT) for f in findings)

    def test_registry_importable_and_matches_ast_view(self):
        """The pass reads FAULT_SITES by AST (works without the package
        importable); the real import must agree with that view."""
        from caffe_mpi_tpu.tools.lint.doc_drift import (REGISTRY_FILE,
                                                        _registry_sites)
        from caffe_mpi_tpu.utils.resilience import FAULT_SITES
        sites, line = _registry_sites(os.path.join(_ROOT, REGISTRY_FILE))
        assert line > 0
        assert set(sites) == set(FAULT_SITES)
        for site, (_, desc) in sites.items():
            assert desc == FAULT_SITES[site], site


class TestLintCoverage:
    def test_hot_paths_stay_in_the_whole_tree_scan(self):
        """The framework's default scan must keep covering the ISSUE-3/4
        hot paths (they are a subset of the whole-tree roots — dropping
        a root from DEFAULT_SCAN silently un-guards them), and the
        legacy shim must keep naming them for muscle memory."""
        import importlib.util
        assert lint.DEFAULT_SCAN[0] == "caffe_mpi_tpu"
        spec = importlib.util.spec_from_file_location(
            "check_host_syncs",
            os.path.join(_ROOT, "tools", "check_host_syncs.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        targets = set(mod.DEFAULT_TARGETS)
        for needed in ("caffe_mpi_tpu/data/feeder.py",
                       "caffe_mpi_tpu/data/datasets.py",
                       "caffe_mpi_tpu/data/lmdb_io.py",
                       "caffe_mpi_tpu/data/leveldb_io.py",
                       "caffe_mpi_tpu/utils/resilience.py"):
            assert needed in targets, needed


# ---------------------------------------------------------------------------
# the commands the documents tell a reader to run

def _expand_braces(token: str) -> list[str]:
    m = re.search(r"\{([^{}]*,[^{}]*)\}", token)
    if m is None:
        return [token]
    return [t for alt in m.group(1).split(",")
            for t in _expand_braces(token[:m.start()] + alt
                                    + token[m.end():])]


def _commands_under(path: str, heading: str) -> list[str]:
    """Lines of the fenced blocks under `heading`, up to the next heading
    of the same level, comments stripped."""
    text = open(path, encoding="utf-8").read()
    level = heading.split(" ")[0]
    section = text.split(heading + "\n", 1)[1]
    section = re.split(rf"^{level} ", section, maxsplit=1, flags=re.M)[0]
    lines = []
    for block in re.findall(r"^```[^\n]*\n(.*?)^```", section,
                            flags=re.S | re.M):
        for line in block.splitlines():
            line = re.sub(r"(^|\s)#.*$", "", line).strip()
            if line:
                lines.append(line)
    return lines


class TestDocumentedCommands:
    @pytest.mark.parametrize("doc,heading", [
        ("README.md", "## Tests / bench"), ("CLAUDE.md", "## Commands")])
    def test_every_documented_command_names_something_that_exists(
            self, doc, heading):
        """Every script path (`python x.py`, `chiprun .. python3 x.py`,
        `x.sh`) and every `python -m module` in the document's command
        block exists: the block cannot keep sending a reader to a tool
        that was deleted."""
        import importlib.util
        lines = _commands_under(os.path.join(_ROOT, doc), heading)
        assert len(lines) >= 5, f"{doc}: no command block under {heading!r}"
        missing = []
        for line in lines:
            tokens = line.split()
            for i, tok in enumerate(tokens):
                if tok == "-m" and tokens[i - 1].startswith("python"):
                    if importlib.util.find_spec(tokens[i + 1]) is None:
                        missing.append((line, tokens[i + 1]))
                elif re.fullmatch(r"[\w./{},-]+\.(py|sh)", tok):
                    missing += [(line, p) for p in _expand_braces(tok)
                                if not os.path.exists(
                                    os.path.join(_ROOT, p))]
        assert not missing, "\n".join(f"{p}: in `{l}`" for l, p in missing)
