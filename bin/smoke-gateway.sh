#!/usr/bin/env bash
# Smoke-test the request plane end to end: start a gateway (admission +
# 2 replica lanes + live swap) over a toy pipeline on an ephemeral
# port, POST a /predict, scrape /metrics for the gateway series,
# trigger one FORCED live engine swap via POST /swap, verify traffic
# still predicts after it, then POST /drain and assert /readyz flips to
# 503 while already-admitted work resolves. CI-friendly: CPU backend,
# ~20s, no network beyond localhost.
#
#   bin/smoke-gateway.sh
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

# the gateway demo entry on port 0 (ephemeral); patched to report the
# bound port to $PORT_FILE via a tiny wrapper. A deliberately
# unmeetable latency SLO (0.1 ms) makes every request an injected-slow
# request: burn gauges light up, the flight recorder captures span
# trees, and the latency histogram carries trace_id exemplars.
# KEYSTONE_PEAK_* pin a fake hardware peak so MFU/roofline light up on
# the CPU backend (absent without them — graceful degradation)
JAX_PLATFORMS=cpu KEYSTONE_PEAK_FLOPS=1e12 KEYSTONE_PEAK_MEMBW_GBPS=100 \
    PYTHONPATH="$ROOT" python - "$PORT_FILE" >"$SERVER_LOG" 2>&1 <<'PY' &
import sys, time
import jax.numpy as jnp
from keystone_tpu.gateway import Gateway, GatewayServer
from keystone_tpu.observability import enable_tracing
from keystone_tpu.serving.demo_model import build_pipeline

enable_tracing()
fitted = build_pipeline(d=8, hidden=8, depth=2)
gateway = Gateway(
    fitted, buckets=(4, 8), n_lanes=2,
    warmup_example=jnp.zeros((8,), jnp.float32), name="smoke",
    slo_latency_s=0.0001, slo_sample_interval_s=0.5,
)
server = GatewayServer(gateway, port=0).start()
with open(sys.argv[1], "w") as f:
    f.write(str(server.port))
time.sleep(120)  # hold the plane alive for the drill
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
echo "gateway up on $BASE"

fetch() {  # fetch <url> [timeout_s] — curl when present, stdlib urllib otherwise
    local timeout="${2:-15}"
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time "$timeout" "$1"
    else
        python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=float(sys.argv[2])).read().decode())' \
            "$1" "$timeout"
    fi
}

fetch_om() {  # fetch with the OpenMetrics Accept header (exemplars)
    if command -v curl >/dev/null 2>&1; then
        curl -fsS --max-time 15 \
            -H 'Accept: application/openmetrics-text' "$1"
    else
        python -c 'import sys, urllib.request; \
req = urllib.request.Request(sys.argv[1], \
headers={"Accept": "application/openmetrics-text"}); \
sys.stdout.write(urllib.request.urlopen(req, timeout=15).read().decode())' "$1"
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

status_of() {  # status_of <url> — status code even for non-2xx
    python -c 'import sys, urllib.request, urllib.error
try:
    print(urllib.request.urlopen(sys.argv[1], timeout=15).status)
except urllib.error.HTTPError as e:
    print(e.code)' "$1"
}

READY="$(fetch "$BASE/readyz")"
[[ "$READY" == "ok" ]] || { echo "FAIL: /readyz said '$READY'"; exit 1; }
echo "PASS /readyz"

PRED="$(post "$BASE/predict" '{"instances": [[1,0,1,0,1,0,1,0], [0,1,0,1,0,1,0,1]]}')"
grep -q '"predictions"' <<<"$PRED" || {
    echo "FAIL: /predict returned: $PRED"; exit 1; }
echo "PASS /predict"

METRICS="$(fetch "$BASE/metrics")"
for want in \
    'keystone_gateway_requests_total{gateway="smoke",status="ok"} 2' \
    'keystone_gateway_request_latency_seconds_bucket{gateway="smoke",le="+Inf"} 2' \
    'keystone_gateway_queue_wait_seconds_count{gateway="smoke"} 2' \
    'keystone_gateway_ready{gateway="smoke"} 1' \
    '# TYPE keystone_gateway_request_latency_seconds histogram' \
    'keystone_serving_examples_total{engine="smoke-lane0"}'
do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing: $want"; echo "$METRICS"; exit 1; }
done
echo "PASS /metrics ($(grep -c '^keystone_gateway' <<<"$METRICS") gateway lines)"

# staged lane pipeline: every lane dispatches through host-prep ->
# upload -> compute -> deliver stage threads (pipeline_depth=2 is the
# gateway default), so the per-stage seconds series, window counter,
# bottleneck attribution, and overlap-efficiency gauge must be on the
# scrape, and /tracez must show the per-stage spans parented under the
# window's microbatch.coalesce span
for want in \
    'keystone_serving_stage_seconds_count{engine="smoke-lane0",stage="host_prep"}' \
    'keystone_serving_stage_seconds_count{engine="smoke-lane0",stage="upload"}' \
    'keystone_serving_stage_seconds_count{engine="smoke-lane0",stage="compute"}' \
    'keystone_serving_stage_seconds_count{engine="smoke-lane0",stage="deliver"}' \
    'keystone_serving_pipeline_windows_total{engine="smoke-lane0"}' \
    '# TYPE keystone_serving_pipeline_bottleneck gauge' \
    'keystone_serving_pipeline_overlap_efficiency{engine="smoke-lane0"}' \
    'keystone_serving_stage_queue_depth{engine="smoke-lane0",stage="host_prep"}'
do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing pipeline series: $want"
        echo "$METRICS" | grep keystone_serving || true; exit 1; }
done
echo "PASS /metrics pipeline stage series"

# device-truth plane on the GATEWAY port: per-bucket cost models from
# each lane engine's warmup, live goodput/padding-efficiency, MFU +
# roofline (pinned peaks), staging-buffer bytes from the lane pools,
# the device info gauge, and the memory sampler the GatewayServer runs
for want in \
    'keystone_device_flops_per_dispatch{engine="smoke-lane0",bucket="4"}' \
    'keystone_serving_goodput_rows_total{engine="smoke-lane0",bucket="' \
    'keystone_serving_padding_efficiency{engine="smoke-lane0"}' \
    'keystone_serving_mfu{engine="smoke-lane0"}' \
    'keystone_device_roofline_bound{engine="smoke-lane0",bucket="4",bound="' \
    'keystone_serving_staging_bytes{engine="smoke-lane0"}' \
    'keystone_device_info{kind="' \
    'keystone_device_memory_bytes{device="host",kind="host-ram",stat="limit"}'
do
    grep -qF "$want" <<<"$METRICS" || {
        echo "FAIL: /metrics missing device-truth series: $want"
        echo "$METRICS" | grep -E 'keystone_(device|serving_(goodput|padd|mfu|stag))' || true
        exit 1; }
done
echo "PASS /metrics device-truth series (cost model, goodput, MFU, roofline, memory)"

# on-demand profiling mirrored on the gateway port; first start_trace
# initializes the profiler backend (~10s observed) — allow extra time
PROFILEZ="$(fetch "$BASE/profilez?seconds=1" 45)"
grep -q '"trace_dir"' <<<"$PROFILEZ" || {
    echo "FAIL: /profilez returned: $PROFILEZ"; exit 1; }
echo "PASS /profilez (on-demand jax.profiler capture while serving)"

TRACEZ="$(fetch "$BASE/tracez")"
for span in pipeline.host_prep pipeline.upload pipeline.compute \
    pipeline.deliver microbatch.coalesce gateway.admit
do
    grep -qF "\"$span\"" <<<"$TRACEZ" || {
        echo "FAIL: /tracez missing span: $span"; exit 1; }
done
# the stage spans carry the coalesce span as parent (cross-thread link)
printf '%s' "$TRACEZ" | python -c '
import json, sys
doc = json.load(sys.stdin)
spans = {}
for s in doc["spans"]:
    spans.setdefault(s["name"], []).append(s)
coalesce_ids = {s["span_id"] for s in spans.get("microbatch.coalesce", [])}
for name in ("pipeline.host_prep", "pipeline.upload",
             "pipeline.compute", "pipeline.deliver"):
    assert any(
        s.get("parent_id") in coalesce_ids for s in spans.get(name, [])
    ), f"{name} spans are not parented under microbatch.coalesce"
print("stage span chain OK")
' || exit 1
echo "PASS /tracez pipeline stage spans"

# forensic chain: the SLO objectives render at /slz with burn rates,
# the injected-slow requests are tail-sampled at /debugz with their
# span trees, and the latency histogram links to them via exemplars
fetch "$BASE/slz" | grep -q '"smoke:latency"' || {
    echo "FAIL: /slz missing the smoke:latency SLO"; exit 1; }
echo "PASS /slz"
DEBUGZ="$(fetch "$BASE/debugz")"
grep -q '"slo_breach"' <<<"$DEBUGZ" || {
    echo "FAIL: /debugz has no slo_breach record"; echo "$DEBUGZ"; exit 1; }
grep -q '"gateway.admit"' <<<"$DEBUGZ" || {
    echo "FAIL: /debugz record is missing its span tree"; exit 1; }
echo "PASS /debugz (injected-slow request captured with span tree)"
# exemplars only travel in the OpenMetrics rendering (the classic
# v0.0.4 parser would reject the mid-line '#'), so scrape with the
# Accept header a real Prometheus server sends; the plain scrape above
# must stay exemplar-free
OM_METRICS="$(fetch_om "$BASE/metrics")"
grep -q '# {trace_id="' <<<"$OM_METRICS" || {
    echo "FAIL: openmetrics /metrics has no trace_id exemplar"; exit 1; }
grep -q '# {trace_id="' <<<"$METRICS" && {
    echo "FAIL: classic /metrics scrape carries exemplar tails"; exit 1; }
echo "PASS exemplars (openmetrics only)"

SWAP="$(post "$BASE/swap" '{}')"
grep -q '"swapped": *true' <<<"$SWAP" || {
    echo "FAIL: /swap returned: $SWAP"; exit 1; }
PRED2="$(post "$BASE/predict" '{"instances": [[1,1,1,1,1,1,1,1]]}')"
grep -q '"predictions"' <<<"$PRED2" || {
    echo "FAIL: post-swap /predict returned: $PRED2"; exit 1; }
fetch "$BASE/metrics" | grep -qF \
    'keystone_gateway_engine_swaps_total{gateway="smoke"} 1' || {
    echo "FAIL: swap counter missing after /swap"; exit 1; }
echo "PASS /swap (forced live engine swap, traffic still serving)"

post "$BASE/drain" '{}' >/dev/null
for _ in $(seq 1 40); do
    [[ "$(status_of "$BASE/readyz")" == "503" ]] && break
    sleep 0.25
done
CODE="$(status_of "$BASE/readyz")"
[[ "$CODE" == "503" ]] || {
    echo "FAIL: /readyz still $CODE after /drain"; exit 1; }
echo "PASS /readyz flipped to 503 during drain"
echo "smoke-gateway: all checks passed"
