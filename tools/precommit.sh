#!/bin/sh
# Pre-commit gate (ISSUE 20 satellite): lint only the files changed
# since <ref> (default HEAD), then hold the lint framework's own suite
# green. Both steps are CPU-only and jax-free. A typo'd ref exits 2 through tpulint's --changed
# contract (never false-clean); any finding exits 1.
#
# Usage: tools/precommit.sh [ref]
set -e
ref="${1:-HEAD}"
cd "$(dirname "$0")/.."
python -m caffe_mpi_tpu.tools.lint --changed "$ref"
python -m pytest tests/test_lint.py -q
