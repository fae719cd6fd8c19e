#!/usr/bin/env bash
# Tier-1 CI: the full test suite on the CPU.  Speed is measured on a
# chip only, with `python3 bench/run.py --workload <cell>`.
#
#   ./scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests =="
python -m pytest -x -q
echo "CI OK"
