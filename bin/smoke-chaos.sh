#!/usr/bin/env bash
# Smoke-test the load-generator + chaos harness end to end:
#
#   1. a real two-process drill — serve-gateway with a file-backed
#      --request-log, serve-loadgen replaying a synthetic Poisson
#      trace against it over HTTP with gateway.lane.kill armed
#      mid-run via POST /chaosz, verdict must be green, and
#      keystone_fault_injections_total{point="gateway.lane.kill"}
#      must show on the gateway's own /metrics;
#   2. record/replay — the request log the drill produced is parsed
#      and replayed back at 8x (the satellite: logs are replayable,
#      no process-output scraping).
#
# CI-friendly: CPU backend, localhost only, ~1 min.
#
#   bin/smoke-chaos.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
REQ_LOG="$TMPDIR/requests.jsonl"
VERDICT="$TMPDIR/verdict.json"
LOADGEN_LOG="$TMPDIR/loadgen.log"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

D=64

# ---- 1. two-process drill over HTTP --------------------------------------
echo "== gateway + loadgen drill (two processes) =="
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --d "$D" --hidden "$D" --depth 2 --buckets 4,16 --lanes 2 \
    --no-cache --request-log "$REQ_LOG" >"$SERVER_LOG" 2>&1 &
SERVER_PID=$!

BASE=""
for _ in $(seq 1 240); do
    BASE="$(grep -o 'http://127.0.0.1:[0-9]*' "$SERVER_LOG" | head -1 || true)"
    [[ -n "$BASE" ]] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "FAIL: gateway died before binding"; cat "$SERVER_LOG"; exit 1; }
    sleep 0.5
done
[[ -n "$BASE" ]] || { echo "FAIL: no gateway URL after 120s"; cat "$SERVER_LOG"; exit 1; }
echo "gateway up on $BASE"

fetch() {  # fetch <url> [timeout_s]
    local timeout="${2:-15}"
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time "$timeout" "$1"
    else
        python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=float(sys.argv[2])).read().decode())' \
            "$1" "$timeout"
    fi
}

post() {  # post <url> <json-body>
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time 30 -X POST -H 'Content-Type: application/json' \
            -d "$2" "$1"
    else
        python -c 'import sys, urllib.request; \
req = urllib.request.Request(sys.argv[1], data=sys.argv[2].encode(), \
headers={"Content-Type": "application/json"}); \
sys.stdout.write(urllib.request.urlopen(req, timeout=30).read().decode())' "$1" "$2"
    fi
}

# the fault-point catalog is served before anything is armed
fetch "$BASE/chaosz" | grep -q '"gateway.lane.kill"' || {
    echo "FAIL: /chaosz catalog missing gateway.lane.kill"; exit 1; }
echo "PASS /chaosz catalog"

# a /chaosz arm/disarm round-trip from the shell (the loadgen below
# arms its own fault the same way, mid-run)
post "$BASE/chaosz" '{"arm": {"point": "otlp.export.blackhole", "count": 1}}' \
    | grep -q '"otlp.export.blackhole"' || {
    echo "FAIL: /chaosz arm did not round-trip"; exit 1; }
post "$BASE/chaosz" '{"disarm": "*"}' | grep -q '"armed": {}' || {
    echo "FAIL: /chaosz disarm did not round-trip"; exit 1; }
echo "PASS /chaosz arm/disarm round-trip"

# open-loop synthetic trace with a lane killed mid-run; the loadgen
# exits nonzero unless the invariant verdict is green. The in-process
# experiments (a lane killed, the prep stage stalled) are
# tests/loadgen/test_runner.py's; this two-process drill also
# fights socket + client-thread scheduling noise on a shared CI
# host, so its tail bound gets headroom — the hard invariants
# (nothing lost, typed-only, readiness back) stay exact — AND one
# bounded retry: the p99-recovery clock races the host scheduler, so
# a single red drill on a loaded box gets one fresh chance (the drill
# is idempotent — it arms its own fault over /chaosz each run and the
# fired-count audit is delta-based) before the smoke fails for real.
DRILL_OK=""
for attempt in 1 2; do
    if JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
        python -m keystone_tpu serve-loadgen --target "$BASE" --d "$D" \
        --synthetic 240 --arrivals poisson --rate 60 \
        --fault 'gateway.lane.kill=lane:0' --fault-at 1.5 --fault-for 1.5 \
        --settle-s 4 --recovery-s 10 --p99-factor 2.0 --max-shed-rate 0.8 \
        --report "$VERDICT" | tee "$LOADGEN_LOG" \
        && grep -q '"passed": true' "$VERDICT"; then
        DRILL_OK=1
        break
    fi
    echo "drill attempt $attempt not green; $([ "$attempt" -lt 2 ] \
        && echo 'retrying once (host-load flake guard)' \
        || echo 'out of retries')"
    cat "$VERDICT" 2>/dev/null || true
done
[[ -n "$DRILL_OK" ]] || {
    echo "FAIL: serve-loadgen drill red on both attempts"; exit 1; }
echo "PASS loadgen drill (verdict green: every admitted request" \
     "resolved, typed sheds only, readiness + p99 recovered)"

# the injections are auditable on the gateway's own scrape surface
fetch "$BASE/metrics" \
    | grep -q 'keystone_fault_injections_total{point="gateway.lane.kill"}' || {
    echo "FAIL: /metrics missing keystone_fault_injections_total"; exit 1; }
echo "PASS /metrics keystone_fault_injections_total{point=\"gateway.lane.kill\"}"

# ---- 2. record/replay ----------------------------------------------------
[[ -s "$REQ_LOG" ]] || { echo "FAIL: --request-log file is empty"; exit 1; }
grep -q '"n_rows"' "$REQ_LOG" && grep -q '"shape"' "$REQ_LOG" || {
    echo "FAIL: request log lines missing the replay fields"; exit 1; }
LINES="$(wc -l < "$REQ_LOG")"
echo "request log captured $LINES lines; replaying at 8x"
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-loadgen --target "$BASE" --d "$D" \
    --trace "$REQ_LOG" --speed 8 --no-verdict \
    | grep -q '"stats"' || {
    echo "FAIL: trace replay did not complete"; exit 1; }
echo "PASS record/replay (the drill's own request log replayed back)"

post "$BASE/drain" '{}' >/dev/null || true
echo "smoke-chaos: all checks passed"
