#!/usr/bin/env bash
# Smoke-test the observability plane end to end: start a toy serving
# engine with the admin endpoint on an ephemeral port, scrape /healthz
# and /metrics, verify the per-bucket serving counters are present, and
# exit nonzero on any failure. CI-friendly: CPU backend, ~15s, no
# network beyond localhost.
#
#   bin/smoke-admin.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
PORT_FILE="$TMPDIR/port"
SERVER_LOG="$TMPDIR/server.log"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

# toy engine + admin endpoint on port 0 (ephemeral); writes the real
# port to $PORT_FILE, serves a little traffic, then idles until killed.
# KEYSTONE_PEAK_* pin a fake hardware peak so the MFU gauge and the
# roofline classification light up on the CPU backend too (unset, those
# series are simply absent — the graceful-degradation contract).
JAX_PLATFORMS=cpu KEYSTONE_PEAK_FLOPS=1e12 KEYSTONE_PEAK_MEMBW_GBPS=100 \
    PYTHONPATH="$ROOT" python - "$PORT_FILE" >"$SERVER_LOG" 2>&1 <<'PY' &
import sys, time
import numpy as np
from keystone_tpu.observability import enable_tracing, start_admin_server
from keystone_tpu.serving.demo_model import build_pipeline

enable_tracing()
server = start_admin_server(port=0)
fitted = build_pipeline(d=8, hidden=8, depth=2)
engine = fitted.compiled(buckets=(4, 8), name="smoke")
# warmup registers each bucket program's XLA cost model (flops/bytes)
engine.warmup(example=np.zeros((8,), np.float32))
rng = np.random.default_rng(0)
engine.apply(rng.standard_normal((3, 8)).astype(np.float32), sync=True)
engine.apply(rng.standard_normal((7, 8)).astype(np.float32), sync=True)
with open(sys.argv[1], "w") as f:
    f.write(str(server.port))
time.sleep(120)  # hold the engine + endpoint alive for the scrape
PY
SERVER_PID=$!

for _ in $(seq 1 120); do
    [[ -s "$PORT_FILE" ]] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "FAIL: server process died before binding"; cat "$SERVER_LOG"; exit 1; }
    sleep 0.5
done
[[ -s "$PORT_FILE" ]] || { echo "FAIL: no port after 60s"; cat "$SERVER_LOG"; exit 1; }
PORT="$(cat "$PORT_FILE")"
BASE="http://127.0.0.1:$PORT"
echo "admin endpoint up on $BASE"

fetch() {  # fetch <url> [timeout_s] — curl when present, stdlib urllib otherwise
    local timeout="${2:-10}"
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time "$timeout" "$1"
    else
        python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=float(sys.argv[2])).read().decode())' \
            "$1" "$timeout"
    fi
}

HEALTH="$(fetch "$BASE/healthz")"
[[ "$HEALTH" == "ok" ]] || { echo "FAIL: /healthz said '$HEALTH'"; exit 1; }
echo "PASS /healthz"

METRICS="$(fetch "$BASE/metrics")"
for want in \
    'keystone_serving_compiles_total{engine="smoke",bucket="4"} 1' \
    'keystone_serving_compiles_total{engine="smoke",bucket="8"} 1' \
    'keystone_serving_dispatches_total{engine="smoke",bucket="4"} 1' \
    'keystone_serving_examples_total{engine="smoke"} 10' \
    'quantile="0.99"' \
    '# TYPE keystone_serving_dispatch_latency_seconds summary'
do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing: $want"; echo "$METRICS"; exit 1; }
done
echo "PASS /metrics ($(grep -c '^keystone_' <<<"$METRICS") keystone series)"

# device-truth plane: per-bucket cost models (flops/bytes from XLA's
# cost analysis at warmup), goodput accounting, the MFU + roofline
# series (lit by the pinned KEYSTONE_PEAK_* env), the detected-device
# info gauge, and the memory sampler (host-RAM fallback on CPU)
for want in \
    'keystone_device_flops_per_dispatch{engine="smoke",bucket="4"}' \
    'keystone_device_flops_per_dispatch{engine="smoke",bucket="8"}' \
    'keystone_device_bytes_per_dispatch{engine="smoke",bucket="4"}' \
    'keystone_serving_goodput_rows_total{engine="smoke",bucket="4"} 3' \
    'keystone_serving_goodput_rows_total{engine="smoke",bucket="8"} 7' \
    'keystone_serving_padded_rows_total{engine="smoke",bucket="4"} 1' \
    'keystone_serving_padding_efficiency{engine="smoke"}' \
    'keystone_serving_mfu{engine="smoke"}' \
    'keystone_device_roofline_bound{engine="smoke",bucket="4",bound="' \
    'keystone_serving_device_flops_total{engine="smoke"}' \
    'keystone_device_info{kind="' \
    'keystone_device_memory_bytes{device="host",kind="host-ram",stat="limit"}'
do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing device-truth series: $want"
        echo "$METRICS" | grep -E 'keystone_(device|serving_(goodput|padd|mfu))' || true
        exit 1; }
done
echo "PASS /metrics device-truth series (cost model, goodput, MFU, roofline, memory)"

fetch "$BASE/tracez" | grep -q '"serving.dispatch"' || {
    echo "FAIL: /tracez has no serving.dispatch span"; exit 1; }
echo "PASS /tracez"

# /slz renders even with no SLOs declared (empty objective list), and
# /varz carries the build/uptime identity block
fetch "$BASE/slz" | grep -q '"slos"' || {
    echo "FAIL: /slz did not render"; exit 1; }
echo "PASS /slz"
VARZ="$(fetch "$BASE/varz")"
for want in '"build"' '"git_sha"' '"uptime_s"' '"jax_version"' \
    '"devices"' '"peak_flops"'; do
    grep -q "$want" <<<"$VARZ" || {
        echo "FAIL: /varz missing $want"; exit 1; }
done
fetch "$BASE/metrics" | grep -q '^keystone_build_info{' || {
    echo "FAIL: /metrics missing keystone_build_info"; exit 1; }
echo "PASS /varz build info + device table"
fetch "$BASE/debugz" | grep -q '"records"' || {
    echo "FAIL: /debugz did not render"; exit 1; }
echo "PASS /debugz"

# on-demand profiling: one /profilez capture returns a trace directory
# listing (jax.profiler XPlane capture, CPU backend included)
# first start_trace in a fresh process initializes the profiler
# backend (~10s observed on this CPU image) — allow well beyond the
# 1s capture window
PROFILEZ="$(fetch "$BASE/profilez?seconds=1" 45)"
grep -q '"trace_dir"' <<<"$PROFILEZ" || {
    echo "FAIL: /profilez returned: $PROFILEZ"; exit 1; }
grep -q '"file_count"' <<<"$PROFILEZ" || {
    echo "FAIL: /profilez capture listed no files: $PROFILEZ"; exit 1; }
echo "PASS /profilez (on-demand jax.profiler capture)"
echo "smoke-admin: all checks passed"
