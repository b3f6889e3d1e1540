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

# serving perf smoke: continuous vs static batching on a mixed-length
# Poisson trace; summary accumulates in BENCH_serving.json
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/serve_continuous.py --smoke --out BENCH_serving.json

# the kv_offload smoke must REPORT its latency hiding: the overlap
# section (trace-derived, counter-validated) with a non-null hidden
# fraction is part of the benchmark contract, not an optional extra
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import json
ov = json.load(open("BENCH_serving.json"))["kv_offload"]["overlap"]
assert ov["hidden_fraction"] is not None, \
    "kv_offload smoke reported no hidden_fraction (no transfer time traced)"
print(f"ci,overlap,hidden_fraction:{ov['hidden_fraction']:.2f}")
EOF

# calibration gate: closing the planning loop on measured bandwidth must
# hide at least as much transfer time as static planning on the same
# trace — the modeled tier's latency is enforced, so this is the paper's
# overlap claim as a hard assert, not a flaky perf check
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import json
cal = json.load(open("BENCH_serving.json"))["calibration"]
hs = cal["static"]["hidden_fraction"]
hc = cal["calibrated"]["hidden_fraction"]
assert hs is not None and hc is not None, "calibration arms traced nothing"
assert hc >= hs, \
    f"calibrated hidden_fraction {hc:.3f} < static {hs:.3f}"
print(f"ci,calibration,hidden_fraction:{hs:.2f}->{hc:.2f},"
      f"workers:{cal['static']['workers']}->{cal['calibrated']['workers']}")
EOF

# decode-kernel gate: the fused paged-decode path must beat the legacy
# per-step gather/concat on decode tok/s with token-identical output
# (codec "none"), and int8 KV pages must at least halve the on-wire
# bytes per pool fetch — the PR's two headline claims as hard asserts
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import json
dk = json.load(open("BENCH_serving.json"))["decode_kernel"]
g = dk["gather"]["tokens_per_s"]
f = dk["fused"]["tokens_per_s"]
assert f > g, f"fused decode {f:.1f} tok/s <= gather {g:.1f}"
assert dk["tokens_match_gather"], "fused decode diverged from gather output"
br = dk["codec"]["byte_reduction"]
assert br >= 2.0, f"int8 pages cut wire bytes only {br:.2f}x (< 2x)"
print(f"ci,decode_kernel,tok/s:{g:.1f}->{f:.1f},"
      f"speedup:{dk['decode_speedup']:.2f},byte_reduction:{br:.2f}")
EOF

# SLO gate: at 3x overload the SLO-aware scheduler must beat FIFO on
# goodput (deadline-met tokens per virtual step) AND on interactive TTFT
# attainment — both on the deterministic virtual clock, so this is a
# hard assert, not a flaky perf check
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import json
o = json.load(open("BENCH_serving.json"))["overload"]["3x"]
fifo, slo = o["fifo"], o["slo"]
gf = fifo["goodput_tokens_per_step"]
gs = slo["goodput_tokens_per_step"]
assert gs > gf, f"SLO goodput {gs:.3f} <= FIFO {gf:.3f} at 3x overload"
tf = fifo["attainment"]["classes"]["interactive"]["ttft_attainment"]
ts = slo["attainment"]["classes"]["interactive"]["ttft_attainment"]
assert ts > tf, f"SLO interactive TTFT attainment {ts:.2f} <= FIFO {tf:.2f}"
print(f"ci,slo_overload_3x,goodput:{gf:.2f}->{gs:.2f},"
      f"ttft_attainment:{tf:.2f}->{ts:.2f}")
EOF

# traced smoke serve: capture one Chrome trace through the launcher's
# telemetry flags and validate it against the repro.obs schema checker
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python launch/serve.py --smoke --requests 4 --trace-out TRACE_smoke.json
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.obs.check TRACE_smoke.json
rm -f TRACE_smoke.json
