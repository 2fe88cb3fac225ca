#!/usr/bin/env python
"""DEPRECATION SHIM — the host-sync lint moved into the framework.

This tool was the single-pass ancestor of `caffe_mpi_tpu.tools.lint`
(ISSUE 5); the pass now lives at caffe_mpi_tpu/tools/lint/host_sync.py,
is scope-aware, and covers the whole tree alongside four sibling
passes. This file keeps the old entry points alive:

    python tools/check_host_syncs.py [file-or-dir ...]

and the module surface (`scan_file`, `scan_paths`, `DEFAULT_TARGETS`,
`WAIVER`) that tests/test_host_sync_lint.py and muscle memory rely on.
New waivers should use the framework grammar
(`# lint: ok(host-sync) — reason`); the legacy `# host-sync: ok`
spelling keeps working.

Prefer: python -m caffe_mpi_tpu.tools.lint --select host-sync [paths]
"""

from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # direct script/importlib execution
    sys.path.insert(0, _ROOT)

from caffe_mpi_tpu.tools import lint as _lint  # noqa: E402

WAIVER = "# host-sync: ok"

# kept for compat: tests assert these stay covered (they are a strict
# subset of the framework's whole-tree default scan)
DEFAULT_TARGETS = ("caffe_mpi_tpu/solver", "caffe_mpi_tpu/parallel",
                   "caffe_mpi_tpu/data/feeder.py",
                   "caffe_mpi_tpu/data/datasets.py",
                   "caffe_mpi_tpu/data/lmdb_io.py",
                   "caffe_mpi_tpu/data/leveldb_io.py",
                   "caffe_mpi_tpu/utils/resilience.py")


def scan_file(path: str) -> list[tuple[str, int, str]]:
    """Return (path, lineno, call-kind) findings for one source file
    (legacy tuple shape; 'SYNTAX ERROR: ...' kind on a broken file)."""
    return [(f.path, f.line, f.detail)
            for f in _lint.run_pass_on_file("host-sync", path)]


def scan_paths(paths) -> list[tuple[str, int, str]]:
    findings = []
    for path in _lint.iter_py_files(paths):
        findings.extend(scan_file(path))
    return findings


def main(argv=None) -> int:
    args = (argv if argv is not None else sys.argv[1:])
    targets = args or [os.path.join(_ROOT, t) for t in DEFAULT_TARGETS]
    findings = scan_paths(targets)
    for path, lineno, kind in findings:
        rel = os.path.relpath(path, _ROOT)
        print(f"{rel}:{lineno}: {kind} inside a hot loop — a device "
              f"value here blocks the host once per iteration; keep it "
              f"on device, or mark the statement `{WAIVER}` if the "
              "sync is deliberate and boundary-rate")
    if findings:
        print(f"{len(findings)} host-sync finding(s)", file=sys.stderr)
        print("note: this tool is a shim; prefer "
              "`python -m caffe_mpi_tpu.tools.lint --select host-sync`",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
