#!/usr/bin/env bash
# Smoke-test zero-cold-start serving end to end:
#
#   1. serve-aot-build pre-populates the AOT serialized-executable
#      store (one compile pass, executables fingerprinted + written);
#   2. a brand-new serve-gateway process starts against that store and
#      must flip /readyz within budget — WITHOUT paying trace/compile:
#      its own /metrics must show keystone_aot_cache_hits_total > 0
#      and keystone_serving_compiles_total must stay absent (no bucket
#      ever traced);
#   3. /predict works, and a forced live swap (POST /swap) rotates
#      next-generation engines that ALSO ride the store (hits or
#      entries grow). The /varz aot_cache status block rides the ADMIN
#      endpoint (not the gateway port this drill uses) and is covered
#      by tests/serving/test_aot.py's varz-status test.
#
# CI-friendly: CPU backend, localhost only, ~1 min.
#
#   bin/smoke-aot.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
AOT_DIR="$TMPDIR/aot"
# readiness budget for the warm start (seconds). Generous for loaded
# CI hosts — the real zero-compile proof is the hit counter below, the
# budget just catches a gateway that silently fell back to compiling
# something pathological.
READY_BUDGET_S=60
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

D=64
SHAPE_ARGS=(--d "$D" --hidden 64 --depth 2 --buckets 4,16)

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

# ---- 1. build the store --------------------------------------------------
echo "== serve-aot-build (populate the executable store) =="
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    KEYSTONE_AOT_CACHE="$AOT_DIR" \
    JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc-build" \
    python -m keystone_tpu serve-aot-build "${SHAPE_ARGS[@]}" \
    | tee "$TMPDIR/build.json"
grep -q '"saved"' "$TMPDIR/build.json" || {
    echo "FAIL: serve-aot-build saved no executables"; exit 1; }
ENTRIES="$(ls "$AOT_DIR"/*.aotx 2>/dev/null | wc -l)"
[[ "$ENTRIES" -ge 2 ]] || {
    echo "FAIL: expected >= 2 store entries, found $ENTRIES"; exit 1; }
echo "PASS store built ($ENTRIES entries in $AOT_DIR)"

# ---- 2. fresh gateway must start hot -------------------------------------
echo "== fresh serve-gateway against the store =="
START_S=$(date +%s)
# a FRESH compile cache dir: the fast start must be attributable to
# the AOT store, not to replayed XLA cache entries
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    KEYSTONE_AOT_CACHE="$AOT_DIR" \
    JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc-fresh" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    "${SHAPE_ARGS[@]}" --lanes 2 >"$SERVER_LOG" 2>&1 &
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

READY=""
for _ in $(seq 1 $((READY_BUDGET_S * 4))); do
    if fetch "$BASE/readyz" 2 >/dev/null 2>&1; then READY=1; break; fi
    sleep 0.25
done
[[ -n "$READY" ]] || {
    echo "FAIL: /readyz not 200 within ${READY_BUDGET_S}s"; cat "$SERVER_LOG"; exit 1; }
ELAPSED=$(( $(date +%s) - START_S ))
[[ "$ELAPSED" -le "$READY_BUDGET_S" ]] || {
    echo "FAIL: ready took ${ELAPSED}s (> ${READY_BUDGET_S}s budget)"; exit 1; }
echo "PASS /readyz in ${ELAPSED}s (budget ${READY_BUDGET_S}s)"

hits_total() {  # sum of keystone_aot_cache_hits_total sample lines
    printf '%s\n' "$1" \
        | awk '$1 == "keystone_aot_cache_hits_total" {s += $2} END {print int(s)}'
}

METRICS="$(fetch "$BASE/metrics")"
HITS="$(hits_total "$METRICS")"
[[ "${HITS:-0}" -gt 0 ]] || {
    echo "FAIL: keystone_aot_cache_hits_total not > 0 on /metrics"
    printf '%s\n' "$METRICS" | grep keystone_aot_cache || true
    exit 1; }
echo "PASS keystone_aot_cache_hits_total = $HITS"
# the strong form of zero-cold-start: NO bucket was ever traced, so
# the per-bucket compile counter never came into existence
if printf '%s\n' "$METRICS" | grep -q 'keystone_serving_compiles_total{'; then
    echo "FAIL: gateway traced/compiled despite a warm store:"
    printf '%s\n' "$METRICS" | grep 'keystone_serving_compiles_total{'
    exit 1
fi
echo "PASS keystone_serving_compiles_total absent (zero traces)"

# ---- 3. traffic + warm-pool swap also ride the store ---------------------
post() {
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time 60 -X POST -H 'Content-Type: application/json' \
            -d "$2" "$1"
    else
        python -c 'import sys, urllib.request; \
req = urllib.request.Request(sys.argv[1], data=sys.argv[2].encode(), \
headers={"Content-Type": "application/json"}); \
sys.stdout.write(urllib.request.urlopen(req, timeout=60).read().decode())' "$1" "$2"
    fi
}
BODY="{\"instances\": [$(python -c "print([0.0]*$D)")]}"
post "$BASE/predict" "$BODY" | grep -q '"predictions"' || {
    echo "FAIL: /predict against the AOT-loaded engines"; exit 1; }
echo "PASS /predict"

# a forced live swap builds next-generation engines THROUGH the store:
# same proposal -> hits grow; a re-bucketed proposal -> fresh entries
# get saved for the next generation. Either way the store must move.
post "$BASE/swap" '{}' | grep -q '"buckets"' || {
    echo "FAIL: POST /swap"; exit 1; }
HITS2="$(hits_total "$(fetch "$BASE/metrics")")"
ENTRIES2="$(ls "$AOT_DIR"/*.aotx 2>/dev/null | wc -l)"
if [[ "${HITS2:-0}" -le "$HITS" && "$ENTRIES2" -le "$ENTRIES" ]]; then
    echo "FAIL: swap moved neither AOT hits ($HITS -> ${HITS2:-0}) nor" \
         "store entries ($ENTRIES -> $ENTRIES2) — next-generation" \
         "engines bypassed the store"
    exit 1
fi
echo "PASS forced swap rode the store (hits $HITS -> $HITS2," \
     "entries $ENTRIES -> $ENTRIES2)"

post "$BASE/drain" '{}' >/dev/null || true
echo "smoke-aot: all checks passed"
