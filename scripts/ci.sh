#!/usr/bin/env bash
# Tier-1 CI gate: install requirements (if anything is missing) and run the
# full test suite. Usage: scripts/ci.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

if ! python -c "import jax, numpy, pytest" 2>/dev/null; then
    python -m pip install --quiet -r requirements.txt
fi

# full suite including slow-marked end-to-end cases (pytest.ini deselects
# them by default so the tier-1 gate stays fast)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -x -q -m "slow or not slow" "$@"

# public-API smoke: the quickstart exercises the OffloadConfig /
# HyperOffloadSession front door end to end (train + serve + stats)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python examples/quickstart.py

# default-config dump: any drift in the public config surface (new field,
# changed default) shows up as a CONFIG_default.json diff in review
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.api --print-config > CONFIG_default.json
