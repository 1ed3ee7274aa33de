#!/usr/bin/env bash
# Tier-1 smoke: the full test suite, Pallas kernels in CPU interpret
# mode. This is what CI runs (see .github/workflows/smoke.yml).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q
