#!/usr/bin/env bash
# Smoke-test the attribution & drift plane end to end:
#
#  1. a real two-model `serve-gateway --zoo --optimize` subprocess:
#     shifted traffic at one model only, then `keystone_drift_score`
#     above threshold for it on /metrics, /driftz carrying a
#     non-empty recommendation-only plan diff, and /attributionz
#     per-model device-FLOP cells reconciling against the engines'
#     own `keystone_serving_device_flops_total` (skipped gracefully
#     when the backend reports no cost analysis);
#  2. keystone-lint self-clean stays at 0 findings (the new
#     metric-family-drift rule included — the catalog table and the
#     registration sites agree).
#
# The in-process form (ledger totals equal to the engines', drift on the
# shifted model only, a re-plan that follows it) is
# tests/observability/test_attribution.py's and test_drift.py's.
#
# CI-friendly: CPU backend, ~1-2 min, no network beyond localhost.
#
#   bin/smoke-attribution.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

echo "== serve-gateway --zoo --optimize drift drill =="
D=6
cat > "$TMPDIR/zoo.json" <<SPEC
{"models": [
  {"name": "alpha", "d": $D, "hidden": 32, "depth": 2, "seed": 1,
   "buckets": [2, 8, 32], "lanes": 1, "default": true, "pinned": true,
   "expected_sizes": {"1": 80, "2": 20}},
  {"name": "beta", "d": $D, "hidden": 32, "depth": 2, "seed": 2,
   "buckets": [2, 8, 32], "lanes": 1,
   "expected_sizes": {"1": 100}}
]}
SPEC
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --zoo "$TMPDIR/zoo.json" --optimize >"$SERVER_LOG" 2>&1 &
SERVER_PID=$!

# with --optimize a {"plan": ...} line precedes the handshake: scan
# every JSON line for the one carrying "listening"
BASE=""
for _ in $(seq 1 240); do
    BASE="$(python - "$SERVER_LOG" <<'PY'
import json, sys
try:
    for line in open(sys.argv[1]):
        line = line.strip()
        if line.startswith("{"):
            doc = json.loads(line)
            if "listening" in doc:
                print(doc["listening"]); break
except Exception:
    pass
PY
)"
    [[ -n "$BASE" ]] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "FAIL: zoo gateway died before binding"; cat "$SERVER_LOG"; exit 1; }
    sleep 0.5
done
[[ -n "$BASE" ]] || { echo "FAIL: no handshake after 120s"; cat "$SERVER_LOG"; exit 1; }
echo "zoo gateway up on $BASE (planned, baselines pinned)"

# shifted mixture: alpha's plan assumed sizes {1,2}, the live traffic
# is all size-24 windows; beta stays on its assumed size-1 mixture
python - "$BASE" "$D" <<'PY'
import json, sys, urllib.request
base, d = sys.argv[1], int(sys.argv[2])

def predict(path, n_rows):
    inst = [[((7 * i + r) % 13) / 13.0 for i in range(d)]
            for r in range(n_rows)]
    req = urllib.request.Request(
        base + path,
        data=json.dumps({"instances": inst}).encode(),
        headers={"Content-Type": "application/json"},
    )
    body = json.loads(urllib.request.urlopen(req, timeout=120).read())
    assert len(body["predictions"]) == n_rows, body
for _ in range(40):
    predict("/predict/alpha", 24)   # shifted: plan assumed 1-2 rows
    predict("/predict/beta", 1)     # on-plan
print("drove 40 shifted alpha requests + 40 on-plan beta requests")
PY

# drift visible on /metrics: alpha above threshold, beta quiet
python - "$BASE" <<'PY'
import sys, urllib.request
body = urllib.request.urlopen(
    sys.argv[1] + "/metrics", timeout=15).read().decode()
scores = {}
for line in body.splitlines():
    if line.startswith("keystone_drift_score{"):
        labels, value = line.rsplit(" ", 1)
        model = labels.split('model="')[1].split('"')[0]
        scores[model] = float(value)
assert "alpha" in scores, f"no alpha drift score exported: {scores}"
assert scores["alpha"] > 0.25, scores
assert scores.get("beta", 0.0) <= 0.25, scores
print(f"drift scores OK: {scores}")
PY
echo "PASS keystone_drift_score rises on the shifted model only"

# /driftz: drifted roster + non-empty recommendation-only plan diff
python - "$BASE" <<'PY'
import json, sys, urllib.request
doc = json.loads(urllib.request.urlopen(
    sys.argv[1] + "/driftz", timeout=15).read())
assert "alpha" in doc["drifted"], doc["drifted"]
assert "beta" not in doc["drifted"], doc["drifted"]
rec = doc.get("recommendation")
assert rec, "drift tripped but /driftz has no recommendation"
assert rec["changes"], rec
assert "alpha" in rec["changes"], rec["changes"]
assert "recommendation only" in rec["note"], rec
print(f"driftz OK: drifted={doc['drifted']} "
      f"changed={sorted(rec['changes'])}")
PY
echo "PASS /driftz non-empty recommendation-only plan diff"

# /attributionz reconciles against the engines' own FLOP counters
python - "$BASE" <<'PY'
import json, sys, urllib.request
base = sys.argv[1]
attr = json.loads(urllib.request.urlopen(
    base + "/attributionz", timeout=15).read())
models = attr["models"]
assert set(models) >= {"alpha", "beta"}, models
assert all(m["goodput_rows"] > 0 for m in models.values()), models
metrics = urllib.request.urlopen(
    base + "/metrics", timeout=15).read().decode()
engine_flops = sum(
    float(line.rsplit(" ", 1)[1])
    for line in metrics.splitlines()
    if line.startswith("keystone_serving_device_flops_total{")
)
ledger_flops = attr["totals"]["device_flops"]
if engine_flops == 0.0:
    # backend reported no cost analysis: absent-not-zero contract
    assert ledger_flops == 0.0, attr["totals"]
    print("attribution OK (no cost analysis on this backend; "
          f"rows={attr['totals']['goodput_rows']})")
else:
    rel = abs(ledger_flops - engine_flops) / engine_flops
    assert rel <= 1e-6, (ledger_flops, engine_flops, rel)
    print(f"attribution OK: ledger {ledger_flops:.3e} FLOPs == "
          f"engines {engine_flops:.3e} (rel err {rel:.1e})")
PY
echo "PASS /attributionz reconciles with engine FLOP counters"

kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""

echo "== keystone-lint self-clean =="
PYTHONPATH="$ROOT" python -m keystone_tpu keystone-lint
echo "PASS keystone-lint 0 findings"

echo "smoke-attribution: all checks passed"
