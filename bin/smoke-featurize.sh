#!/usr/bin/env bash
# Smoke-test device-side featurization end to end:
#
#  1. a real `serve-gateway --device-featurize` subprocess (demo
#     chain): POST a raw uint8 image to /predict, assert predictions
#     come back and that `keystone_serving_h2d_bytes_total` is on
#     /metrics with the raw byte footprint (bucket * img * img * 3) —
#     the wire-bytes win as a scraped fact;
#  2. the same drill against `--device-featurize flagship` — the
#     branched Pallas-kernel chain behind the same gateway seam.
#
# Fused output against the host path and the H2D bytes per row of the two
# wire formats are held by tests/serving/test_device_featurize.py.
#
# CI-friendly: CPU backend, ~1-2 min, no network beyond localhost.
#
#   bin/smoke-featurize.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

echo "== serve-gateway --device-featurize drill =="
IMG=8
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --device-featurize --img "$IMG" --buckets 4,8 --lanes 1 \
    --hidden 64 --depth 2 >"$SERVER_LOG" 2>&1 &
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
        echo "FAIL: gateway died before binding"; cat "$SERVER_LOG"; exit 1; }
    sleep 0.5
done
[[ -n "$BASE" ]] || { echo "FAIL: no handshake after 120s"; cat "$SERVER_LOG"; exit 1; }
echo "gateway up on $BASE"

# one raw uint8 image instance (IMG x IMG x 3 nested JSON ints)
PRED="$(python - "$BASE" "$IMG" <<'PY'
import json, sys, urllib.request
base, img = sys.argv[1], int(sys.argv[2])
inst = [[[x % 251, y % 251, (x + y) % 251] for y in range(img)]
        for x in range(img)]
req = urllib.request.Request(
    base + "/predict",
    data=json.dumps({"instances": [inst]}).encode(),
    headers={"Content-Type": "application/json"},
)
print(urllib.request.urlopen(req, timeout=60).read().decode())
PY
)"
grep -q '"predictions"' <<<"$PRED" || {
    echo "FAIL: /predict returned: $PRED"; cat "$SERVER_LOG"; exit 1; }
echo "PASS /predict (raw uint8 image in, predictions out)"

# malformed raw payload: a pixel out of uint8 range is the CLIENT's
# error — typed 400 bad_request, never a 500 + server stack trace
BADCODE="$(python - "$BASE" <<'PY'
import json, sys, urllib.request, urllib.error
req = urllib.request.Request(
    sys.argv[1] + "/predict",
    data=json.dumps({"instances": [[[[256, 0, 0]]]]}).encode(),
    headers={"Content-Type": "application/json"},
)
try:
    print(urllib.request.urlopen(req, timeout=30).status)
except urllib.error.HTTPError as e:
    print(e.code)
PY
)"
[[ "$BADCODE" == "400" ]] || {
    echo "FAIL: out-of-range pixel returned $BADCODE, want 400"; exit 1; }
echo "PASS /predict out-of-range pixel -> 400 bad_request"

METRICS="$(python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=15).read().decode())' \
    "$BASE/metrics")"
# the single-instance window dispatches bucket 4: 4 * IMG*IMG*3 raw
# uint8 bytes staged — raw-on-the-wire, exactly accounted
WANT_BYTES=$((4 * IMG * IMG * 3))
grep -qF "keystone_serving_h2d_bytes_total{engine=\"gateway-lane0\",bucket=\"4\"} $WANT_BYTES" \
    <<<"$METRICS" || {
    echo "FAIL: /metrics missing the h2d bytes counter ($WANT_BYTES expected):"
    grep keystone_serving_h2d <<<"$METRICS" || true
    exit 1; }
echo "PASS /metrics keystone_serving_h2d_bytes_total ($WANT_BYTES raw bytes)"

kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""

echo "== serve-gateway --device-featurize flagship drill =="
# img must clear the LCS border (> 32); 34 keeps the CPU warmup quick
FIMG=34
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --device-featurize flagship --img "$FIMG" --buckets 4,8 --lanes 1 \
    --hidden 64 --depth 2 >"$SERVER_LOG.flagship" 2>&1 &
SERVER_PID=$!

BASE=""
for _ in $(seq 1 240); do
    BASE="$(python - "$SERVER_LOG.flagship" <<'PY'
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
        echo "FAIL: flagship gateway died before binding"
        cat "$SERVER_LOG.flagship"; exit 1; }
    sleep 0.5
done
[[ -n "$BASE" ]] || {
    echo "FAIL: no flagship handshake after 120s"
    cat "$SERVER_LOG.flagship"; exit 1; }
echo "flagship gateway up on $BASE"

PRED="$(python - "$BASE" "$FIMG" <<'PY'
import json, sys, urllib.request
base, img = sys.argv[1], int(sys.argv[2])
inst = [[[x % 251, y % 251, (x + y) % 251] for y in range(img)]
        for x in range(img)]
req = urllib.request.Request(
    base + "/predict",
    data=json.dumps({"instances": [inst]}).encode(),
    headers={"Content-Type": "application/json"},
)
print(urllib.request.urlopen(req, timeout=120).read().decode())
PY
)"
grep -q '"predictions"' <<<"$PRED" || {
    echo "FAIL: flagship /predict returned: $PRED"
    cat "$SERVER_LOG.flagship"; exit 1; }
echo "PASS flagship /predict (raw uint8 image through the SIFT+LCS->FV DAG)"

METRICS="$(python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=15).read().decode())' \
    "$BASE/metrics")"
# single instance -> bucket 4: 4 * FIMG*FIMG*3 raw uint8 bytes staged
WANT_BYTES=$((4 * FIMG * FIMG * 3))
grep -qF "keystone_serving_h2d_bytes_total{engine=\"gateway-lane0\",bucket=\"4\"} $WANT_BYTES" \
    <<<"$METRICS" || {
    echo "FAIL: flagship /metrics missing the h2d bytes counter ($WANT_BYTES expected):"
    grep keystone_serving_h2d <<<"$METRICS" || true
    exit 1; }
echo "PASS flagship /metrics keystone_serving_h2d_bytes_total ($WANT_BYTES raw bytes)"

kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""
echo "smoke-featurize: all checks passed"
