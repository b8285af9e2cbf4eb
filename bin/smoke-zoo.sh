#!/usr/bin/env bash
# Smoke-test the model-zoo serving plane end to end:
#
#  1. a real two-model `serve-gateway --zoo` subprocess: per-model
#     POST /predict/<model> (bare /predict serves the default model
#     and must match it bit-for-bit), a typed 404 for an unknown
#     model id enumerating the registered ids, /planz reporting the
#     plan-vs-actual placement, and the `model`-labeled zoo gauges
#     on /metrics;
#  2. keystone-lint self-clean stays at 0 findings (the zoo subsystem
#     plays by the repo's own rules).
#
# Cross-model CSE itself (one SharedPrefixEngine, per-model parity, the
# prefix compiled once per bucket, fewer dispatches) is held by
# tests/zoo/test_cse.py.
#
# CI-friendly: CPU backend, ~1 min, no network beyond localhost.
#
#   bin/smoke-zoo.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

echo "== serve-gateway --zoo drill (two models, one port) =="
D=24
cat > "$TMPDIR/zoo.json" <<SPEC
{"models": [
  {"name": "alpha", "d": $D, "hidden": 32, "depth": 2, "seed": 1,
   "buckets": [4, 8], "lanes": 1, "default": true, "pinned": true},
  {"name": "beta", "d": $D, "hidden": 32, "depth": 2, "seed": 2,
   "buckets": [4, 8], "lanes": 1}
]}
SPEC
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --zoo "$TMPDIR/zoo.json" >"$SERVER_LOG" 2>&1 &
SERVER_PID=$!

BASE=""
for _ in $(seq 1 240); do
    BASE="$(python - "$SERVER_LOG" <<'PY'
import json, sys
try:
    for line in open(sys.argv[1]):
        line = line.strip()
        if line.startswith("{"):
            print(json.loads(line)["listening"]); break
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
grep -q '"models": \["alpha", "beta"\]' "$SERVER_LOG" || {
    echo "FAIL: handshake line missing the model roster"; cat "$SERVER_LOG"; exit 1; }
echo "zoo gateway up on $BASE serving [alpha, beta]"

# per-model routing + default-model parity + head divergence, one shot
python - "$BASE" "$D" <<'PY'
import json, sys, urllib.request
base, d = sys.argv[1], int(sys.argv[2])
inst = [((7 * i) % 13) / 13.0 for i in range(d)]

def predict(path):
    req = urllib.request.Request(
        base + path,
        data=json.dumps({"instances": [inst]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    body = json.loads(urllib.request.urlopen(req, timeout=120).read())
    return body["predictions"]

bare = predict("/predict")
alpha = predict("/predict/alpha")
beta = predict("/predict/beta")
assert bare == alpha, (
    f"bare /predict must serve the DEFAULT model: {bare} != {alpha}")
assert alpha != beta, (
    "alpha and beta returned identical predictions — the zoo is not "
    f"routing per model ({alpha})")
print(f"per-model routing OK: alpha={alpha} beta={beta} (bare==alpha)")
PY
echo "PASS /predict/<model> routing + default-model parity"

# unknown model id: typed 404 enumerating the registered ids
python - "$BASE" "$D" <<'PY'
import json, sys, urllib.request, urllib.error
base, d = sys.argv[1], int(sys.argv[2])
req = urllib.request.Request(
    base + "/predict/nope",
    data=json.dumps({"instances": [[0.0] * d]}).encode(),
    headers={"Content-Type": "application/json"},
)
try:
    urllib.request.urlopen(req, timeout=30)
    raise SystemExit("FAIL: unknown model id did not 404")
except urllib.error.HTTPError as e:
    assert e.code == 404, f"want 404, got {e.code}"
    body = json.loads(e.read())
    assert body["error"] == "unknown_model", body
    assert sorted(body["registered"]) == ["alpha", "beta"], body
    print(f"unknown-model 404 OK: {body}")
PY
echo "PASS unknown model -> typed 404 with registered ids"

# /planz: the placement report knows both models and who is resident
python - "$BASE" <<'PY'
import json, sys, urllib.request
plan = json.loads(urllib.request.urlopen(
    sys.argv[1] + "/planz", timeout=15).read())
assert plan["default_model"] == "alpha", plan
actual = plan["actual"]
assert set(actual) == {"alpha", "beta"}, plan
assert actual["alpha"]["resident"] is True, plan
assert actual["alpha"]["pinned"] is True, plan
print(f"planz OK: default={plan['default_model']} "
      f"resident={[m for m, a in actual.items() if a['resident']]}")
PY
echo "PASS /planz plan-vs-actual"

METRICS="$(python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=15).read().decode())' \
    "$BASE/metrics")"
for want in \
    'keystone_zoo_resident{model="alpha"} 1' \
    'keystone_zoo_resident{model="beta"} 1' \
    'keystone_zoo_pageins_total{model="beta"} 1'; do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing '$want':"
        grep keystone_zoo <<<"$METRICS" || true
        exit 1; }
done
echo "PASS /metrics model-labeled zoo gauges"

kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""

echo "== keystone-lint self-clean =="
PYTHONPATH="$ROOT" python -m keystone_tpu keystone-lint
echo "PASS keystone-lint 0 findings"

echo "smoke-zoo: all checks passed"
