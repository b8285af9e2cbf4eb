#!/usr/bin/env bash
# Smoke-test the fleet tier end to end:
#
#   1. a real THREE-process drill — serve-router + two serve-gateway
#      replicas that self-register (--register) after binding
#      ephemeral ports (--gateway-port 0 prints the bound address as
#      a parseable JSON line — no port races), both pointed at ONE
#      shared KEYSTONE_AOT_CACHE so replica #2 must start warm
#      (keystone_aot_cache_hits_total > 0 on its own /metrics);
#   2. chaos across hosts — serve-loadgen replays a synthetic trace
#      through the ROUTER while replica #1's process is kill -9'd
#      mid-load; the invariant checker must report green (zero lost
#      futures, typed sheds only) and /fleetz must show the replica
#      leave the healthy set;
#   3. half-open recovery — replica #1 restarts AT THE SAME PORT;
#      /fleetz must show it healthy again once router traffic
#      half-opens and restores it;
#   4. SLO federation — histogram_quantile over the router's
#      federated /metrics must agree with the per-replica quantiles
#      to within one bucket boundary;
#   5. distributed tracing — one /predict through the three-process
#      drill must come back with an X-Keystone-Trace id that appears
#      in BOTH processes' /tracez and stitches at the router's
#      /debugz?trace_id= into one tree with spans from both processes
#      and a phase decomposition summing to within 10% of the
#      measured total.
#
# The in-process form (a router over two HTTP replicas, one black-holed
# mid-run, verdict green, both replicas in the federated histogram) is
# tests/fleet/test_router_http.py's.
#
# CI-friendly: CPU backend, localhost only, ~3 min.
#
#   bin/smoke-fleet.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
ROUTER_LOG="$TMPDIR/router.log"
R1_LOG="$TMPDIR/replica1.log"
R2_LOG="$TMPDIR/replica2.log"
VERDICT="$TMPDIR/verdict.json"
AOT_CACHE="$TMPDIR/aot"
cleanup() {
    for pid in "${ROUTER_PID:-}" "${R1_PID:-}" "${R2_PID:-}"; do
        [[ -n "$pid" ]] && kill "$pid" 2>/dev/null || true
    done
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

D=64
# --trace: replicas adopt the router's W3C traceparent so step 5's
# stitched-trace assertion has both halves to join
GW_ARGS=(--d "$D" --hidden "$D" --depth 2 --buckets 4,16 --lanes 2 --trace)

listen_url() {  # listen_url <logfile> — the parseable {"listening": ...} line
    python -c '
import json, sys
for line in open(sys.argv[1]):
    line = line.strip()
    if line.startswith("{"):
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if "listening" in doc:
            print(doc["listening"])
            break
' "$1"
}

wait_listen() {  # wait_listen <logfile> <pid> <what> -> URL on stdout
    local url=""
    for _ in $(seq 1 240); do
        url="$(listen_url "$1")"
        [[ -n "$url" ]] && { echo "$url"; return 0; }
        kill -0 "$2" 2>/dev/null || {
            echo "FAIL: $3 died before binding" >&2; cat "$1" >&2; return 1; }
        sleep 0.5
    done
    echo "FAIL: no $3 URL after 120s" >&2; cat "$1" >&2; return 1
}

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

# ---- 1. three-process fleet: router + 2 self-registering replicas --------
echo "== three-process drill: router + 2 replicas =="
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-router --router-port 0 \
    --probe-interval 0.5 --recovery-after 2 >"$ROUTER_LOG" 2>&1 &
ROUTER_PID=$!
ROUTER="$(wait_listen "$ROUTER_LOG" "$ROUTER_PID" router)"
echo "router up on $ROUTER"

start_replica() {  # start_replica <logfile> <extra args...>
    local log="$1"; shift
    JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
        KEYSTONE_AOT_CACHE="$AOT_CACHE" \
        python -m keystone_tpu serve-gateway --gateway-port 0 \
        "${GW_ARGS[@]}" --register "$ROUTER" "$@" >"$log" 2>&1 &
}

JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc1" start_replica "$R1_LOG"
R1_PID=$!
R1="$(wait_listen "$R1_LOG" "$R1_PID" replica1)"
# replica 1 fully warm (and the shared AOT store populated) BEFORE
# replica 2 starts, so replica 2's warmup has executables to load
for _ in $(seq 1 240); do
    fetch "$R1/readyz" >/dev/null 2>&1 && break
    sleep 0.5
done
echo "replica1 up on $R1 (cold start populated $AOT_CACHE)"

JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc2" start_replica "$R2_LOG"
R2_PID=$!
R2="$(wait_listen "$R2_LOG" "$R2_PID" replica2)"
for _ in $(seq 1 240); do
    fetch "$R2/readyz" >/dev/null 2>&1 && break
    sleep 0.5
done
echo "replica2 up on $R2"

# the PR 8 follow-on: replica 2 must have started WARM off the shared
# executable store — its own /metrics proves it
fetch "$R2/metrics" | PYTHONPATH="$ROOT" python -c '
import sys
from keystone_tpu.observability.prometheus import parse_samples
hits = sum(v for n, _, v in parse_samples(sys.stdin.read())
           if n == "keystone_aot_cache_hits_total")
assert hits > 0, "replica 2 reported zero AOT cache hits: not a warm start"
print(f"replica2 AOT cache hits: {hits:g}")
' || { echo "FAIL: replica 2 did not start warm off the shared AOT store"; exit 1; }
echo "PASS shared-AOT warm start"

# both replicas self-registered and probed ready
for _ in $(seq 1 60); do
    READY="$(fetch "$ROUTER/fleetz" \
        | python -c 'import json,sys; d=json.load(sys.stdin); \
print(sum(1 for r in d["replicas"] if r["ready"] and r["healthy"]))' )"
    [[ "$READY" == "2" ]] && break
    sleep 0.5
done
[[ "$READY" == "2" ]] || {
    echo "FAIL: /fleetz never showed 2 ready replicas"; fetch "$ROUTER/fleetz"; exit 1; }
echo "PASS self-registration (/fleetz: 2 replicas ready)"

# ---- 2. kill a replica PROCESS mid-load; verdict must stay green ---------
echo "== chaos across hosts: kill -9 replica1 mid-load =="
( sleep 2; kill -9 "$R1_PID" 2>/dev/null || true ) &
KILLER_PID=$!
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-loadgen --target "$ROUTER" --d "$D" \
    --synthetic 240 --arrivals poisson --rate 50 \
    --settle-s 3 --max-shed-rate 0.5 --report "$VERDICT" \
    >"$TMPDIR/loadgen.log" 2>&1 || {
    echo "FAIL: loadgen through the router went red with a replica killed"
    cat "$TMPDIR/loadgen.log"; exit 1; }
wait "$KILLER_PID" 2>/dev/null || true
grep -q '"passed": true' "$VERDICT" || {
    echo "FAIL: invariant verdict not green"; cat "$VERDICT"; exit 1; }
echo "PASS kill-mid-load (every admitted request resolved, typed sheds only)"

# the dead replica left the healthy set
for _ in $(seq 1 30); do
    DEAD_STATE="$(fetch "$ROUTER/fleetz" | python -c '
import json, sys
doc = json.load(sys.stdin)
row = next(r for r in doc["replicas"] if r["url"] == sys.argv[1])
print("dead" if not row["healthy"] else "alive")
' "$R1")"
    [[ "$DEAD_STATE" == "dead" ]] && break
    sleep 0.5
done
[[ "$DEAD_STATE" == "dead" ]] || {
    echo "FAIL: /fleetz still shows the killed replica healthy"
    fetch "$ROUTER/fleetz"; exit 1; }
echo "PASS /fleetz shows killed replica unhealthy"

# ---- 3. restart at the SAME port; half-open recovery -----------------------
echo "== restart replica1; half-open recovery =="
R1_PORT="${R1##*:}"
JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc1" start_replica "$R1_LOG.2" \
    --gateway-port "$R1_PORT"
R1_PID=$!
for _ in $(seq 1 240); do
    fetch "$R1/readyz" >/dev/null 2>&1 && break
    kill -0 "$R1_PID" 2>/dev/null || {
        echo "FAIL: restarted replica1 died"; cat "$R1_LOG.2"; exit 1; }
    sleep 0.5
done
# a little router traffic lets the half-open replica earn its restore
for i in 1 2 3 4 5 6 7 8; do
    python -c '
import json, sys, urllib.request
body = json.dumps({"instances": [[0.0] * int(sys.argv[2])]}).encode()
req = urllib.request.Request(sys.argv[1] + "/predict", data=body,
                             headers={"Content-Type": "application/json"})
urllib.request.urlopen(req, timeout=30).read()
' "$ROUTER" "$D" >/dev/null 2>&1 || true
    sleep 0.5
done
RECOVERED=""
for _ in $(seq 1 60); do
    STATE="$(fetch "$ROUTER/fleetz" | python -c '
import json, sys
doc = json.load(sys.stdin)
row = next(r for r in doc["replicas"] if r["url"] == sys.argv[1])
print(row["state"])
' "$R1")"
    if [[ "$STATE" == "healthy" ]]; then RECOVERED=1; break; fi
    sleep 0.5
done
[[ -n "$RECOVERED" ]] || {
    echo "FAIL: replica1 never recovered to healthy (last state: $STATE)"
    fetch "$ROUTER/fleetz"; exit 1; }
echo "PASS half-open recovery (/fleetz: replica1 healthy after restart)"

# ---- 4. federated quantile agrees with the per-replica quantiles ---------
echo "== SLO federation: fleet quantile vs per-replica quantiles =="
PYTHONPATH="$ROOT" python -c '
import sys, urllib.request
from keystone_tpu.observability.prometheus import (
    histogram_buckets, merge_histograms, quantile_from_buckets)

router, r1, r2 = sys.argv[1:4]
FAMILY = "keystone_gateway_request_latency_seconds"

def scrape(url):
    with urllib.request.urlopen(url + "/metrics", timeout=15) as resp:
        return resp.read().decode()

fed = histogram_buckets(scrape(router), FAMILY)
per = [histogram_buckets(scrape(u), FAMILY) for u in (r1, r2)]
assert fed, "router /metrics had no federated latency buckets"
assert all(per), "a replica scrape had no latency buckets"
# both replicas share the default gateway name, so the router body
# carries ONE summed fleet series; its count must cover both replicas
assert fed[-1][1] >= max(b[-1][1] for b in per), (fed[-1], [b[-1] for b in per])

bounds = [le for le, _ in fed]
def covering(q):
    return next(i for i, le in enumerate(bounds) if q <= le)

qf = quantile_from_buckets(0.99, fed)
qs = [quantile_from_buckets(0.99, b) for b in per]
idx_f, idx = covering(qf), [covering(q) for q in qs]
lo, hi = min(idx) - 1, max(idx) + 1
assert lo <= idx_f <= hi, (
    "federated p99 %.1fms (bucket %d) outside one bucket of "
    "per-replica p99s %sms (buckets %s)"
    % (qf * 1e3, idx_f, [round(q * 1e3, 1) for q in qs], idx))
print("fleet p99 %.1fms agrees with per-replica %sms "
      "within one bucket boundary"
      % (qf * 1e3, [round(q * 1e3, 1) for q in qs]))
' "$ROUTER" "$R1" "$R2" || {
    echo "FAIL: federated quantile disagreed with per-replica quantiles"; exit 1; }
echo "PASS SLO federation"

# ---- 5. distributed tracing: one id, two processes, one stitched tree ----
echo "== distributed tracing: cross-process stitch through the router =="
PYTHONPATH="$ROOT" python -c '
import json, sys, time, urllib.request

router, r1, r2, d = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])

body = json.dumps({"instances": [[0.25] * d]}).encode()
req = urllib.request.Request(router + "/predict", data=body,
                             headers={"Content-Type": "application/json"})
t0 = time.perf_counter()
with urllib.request.urlopen(req, timeout=60) as resp:
    resp.read()
    measured_ms = (time.perf_counter() - t0) * 1e3
    tid = resp.headers.get("X-Keystone-Trace")
assert tid, "/predict response carried no X-Keystone-Trace header"
print(f"trace id {tid} (measured {measured_ms:.1f}ms)")
time.sleep(0.5)  # replica stage spans finish just after the response

def get_json(url):
    with urllib.request.urlopen(url, timeout=15) as resp:
        return json.loads(resp.read())

# the id is visible in the router Tracer ring AND at least one replica
rt = get_json(router + "/tracez")
assert any(s["trace_id"] == tid for s in rt["spans"]), \
    "router /tracez does not show the trace"
replica_hits = [
    url for url in (r1, r2)
    if any(s["trace_id"] == tid
           for s in get_json(url + "/tracez")["spans"])
]
assert replica_hits, "no replica /tracez shows the trace id"
print(f"trace visible in router + {len(replica_hits)} replica /tracez")

# the stitched tree: spans from both processes under ONE trace id
doc = get_json(router + f"/debugz?trace_id={tid}")
assert len(doc["processes"]) >= 2, (
    "stitch is router-only: %s (partial_detail=%s)"
    % (doc["processes"], doc["partial_detail"]))
assert not doc["partial"], doc["partial_detail"]
names = {s["name"] for s in doc["spans"]}
assert "router.forward" in names and "gateway.admit" in names, names
grafted = [s for s in doc["spans"] if s.get("grafted")]
assert grafted, "no replica span was grafted under a router hop"

# chrome render loads as one multi-process trace
chrome = get_json(router + f"/debugz?trace_id={tid}&format=chrome")
pids = {e["pid"] for e in chrome["traceEvents"] if e.get("ph") == "X"}
assert len(pids) >= 2, f"chrome trace has one pid only: {pids}"

# phase decomposition sums to within 10% of the measured request
# latency (the router-measured total). The client clock only bounds
# it from above: client-side connection setup on a loaded host is
# NOT part of the server-side request.
phases = doc["phases_ms"]
total = doc["total_ms"]
ph_sum = sum(phases.values())
assert abs(ph_sum - total) <= 0.1 * total, (phases, total)
assert total <= measured_ms + 1.0, (
    f"stitched total {total}ms exceeds client-measured "
    f"{measured_ms:.1f}ms")
assert total >= 0.2 * measured_ms, (
    f"stitched total {total}ms implausibly small vs client-measured "
    f"{measured_ms:.1f}ms")
print(f"phases {phases} sum {ph_sum:.1f}ms ~ total {total}ms "
      f"(client measured {measured_ms:.1f}ms)")

# the phase family rides the router/federated /metrics
with urllib.request.urlopen(router + "/metrics", timeout=15) as resp:
    fed = resp.read().decode()
assert "keystone_request_phase_seconds_bucket" in fed, \
    "keystone_request_phase_seconds missing from federated /metrics"
print("keystone_request_phase_seconds present in federated /metrics")
' "$ROUTER" "$R1" "$R2" "$D" || {
    echo "FAIL: cross-process trace did not stitch"; exit 1; }
echo "PASS distributed tracing (one trace id, stitched /debugz, phases sum)"

echo "smoke-fleet: all checks passed"
