#!/usr/bin/env bash
# Smoke-test autonomous fleet elasticity end to end:
# the real SUBPROCESS drill — `serve-autoscale` stands up a
# router + supervisor + SLO-driven policy loop and spawns
# serve-gateway replicas as child processes (port-0
# {"listening": ...} handshake, --register self-registration, a
# shared AOT store so scale-out starts warm). Then:
#   a. a `serve-loadgen --ramp` staircase drives the fleet past
#      one replica's capacity — the supervisor must GROW the
#      fleet (scale_up decision events + /fleetz shows >= 2
#      replicas + keystone_autoscale_* series on /metrics);
#   b. MID-SURGE — while the fleet is hot, so no scale-down can
#      race the victim — one replica process is kill -9'd: the
#      supervisor must REPLACE it (replica_died /
#      replicas_replaced events) and the loadgen verdict must
#      stay green through the death;
#   c. the load stops — the control loop must DRAIN-RETIRE back
#      to the 1-replica baseline (scale_down events, /fleetz
#      back to 1, retired replicas deregistered not just dead);
# and the loadgen invariant verdict for the ramp must be green
# (nothing lost, typed sheds only).
#
# The policy, the supervisor and the control loop in one process are
# tests/autoscale/'s; the load ramp is tests/loadgen/test_ramp.py's.
#
# CI-friendly: CPU backend, localhost only, small pipeline, short
# windows/cooldowns (the policy ARITHMETIC is under test, not
# production wall clocks). ~4 min.
#
#   bin/smoke-autoscale.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
AS_LOG="$TMPDIR/autoscale.log"
VERDICT="$TMPDIR/verdict.json"
AOT_CACHE="$TMPDIR/aot"
REPLICA_LOGS="$TMPDIR/replicas"
cleanup() {
    [[ -n "${AS_PID:-}" ]] && kill "$AS_PID" 2>/dev/null || true
    # give the supervisor a moment to drain its children, then sweep
    # any stragglers — matched by THIS run's unique AOT-cache path on
    # their command lines, so a concurrent fleet drill on the same
    # box is never collateral
    sleep 3
    pkill -f "serve-gateway.*$AOT_CACHE" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

D=48

# ---- the subprocess drill - ----------------------------------------------
echo "== serve-autoscale: router + subprocess replicas =="
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    JAX_COMPILATION_CACHE_DIR="$TMPDIR/xc" \
    python -m keystone_tpu serve-autoscale \
    --min-replicas 1 --max-replicas 3 \
    --slo-latency-ms 200 --slo-fast-window 6 --slo-sample-interval 0.5 \
    --interval 1 --up-consecutive 2 --down-consecutive 3 \
    --up-cooldown 3 --down-cooldown 3 \
    --d "$D" --hidden "$D" --depth 2 --buckets 8 --lanes 1 \
    --aot-cache "$AOT_CACHE" --replica-log-dir "$REPLICA_LOGS" \
    --startup-timeout 240 \
    >"$AS_LOG" 2>&1 &
AS_PID=$!

listen_url() {
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
ROUTER=""
for _ in $(seq 1 60); do
    ROUTER="$(listen_url "$AS_LOG")"
    [[ -n "$ROUTER" ]] && break
    kill -0 "$AS_PID" 2>/dev/null || {
        echo "FAIL: serve-autoscale died before binding"; cat "$AS_LOG"; exit 1; }
    sleep 0.5
done
[[ -n "$ROUTER" ]] || { echo "FAIL: no router URL"; cat "$AS_LOG"; exit 1; }
echo "autoscaler router on $ROUTER"

fetch() {
    python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=float(sys.argv[2])).read().decode())' \
        "$1" "${2:-15}"
}

ready_replicas() {
    fetch "$ROUTER/fleetz" | python -c '
import json, sys
doc = json.load(sys.stdin)
print(sum(1 for r in doc["replicas"] if r["ready"] and r["healthy"]))'
}

# the first replica registers and goes ready (cold start populates the
# shared AOT store, so every LATER replica starts warm)
for _ in $(seq 1 240); do
    [[ "$(ready_replicas 2>/dev/null || echo 0)" == "1" ]] && break
    kill -0 "$AS_PID" 2>/dev/null || {
        echo "FAIL: serve-autoscale died"; tail -40 "$AS_LOG"; exit 1; }
    sleep 1
done
[[ "$(ready_replicas)" == "1" ]] || {
    echo "FAIL: first replica never became ready"; tail -40 "$AS_LOG"; exit 1; }
echo "PASS baseline (1 subprocess replica registered + ready)"

# ---- a+b. ramp load -> scale-out; kill -9 MID-SURGE -> replacement -----
echo "== ramp: scale-out under SLO pressure + kill -9 mid-surge =="
# calibrate the surge to this host: time one sequential request and
# offer ~4x that rate (a fixed rate would be a no-op on a fast box)
HIGH_RATE="$(PYTHONPATH="$ROOT" python -c '
import json, sys, time, urllib.request
router, d = sys.argv[1], int(sys.argv[2])
body = json.dumps({"instances": [[0.1] * d] * 8}).encode()
def one():
    req = urllib.request.Request(router + "/predict", data=body,
                                 headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    urllib.request.urlopen(req, timeout=60).read()
    return time.perf_counter() - t0
for _ in range(3): one()
lat = sorted(one() for _ in range(6))
base = lat[len(lat) // 2]
print(min(200, max(10, int(4.0 / max(base, 1e-3)))))
' "$ROUTER" "$D")"
echo "calibrated surge rate: ${HIGH_RATE} rps"
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-loadgen --target "$ROUTER" --d "$D" \
    --ramp "2:4,${HIGH_RATE}:30,2:6" --size-mix 8:1.0 \
    --max-outstanding 64 --settle-s 4 --max-shed-rate 0.9 \
    --report "$VERDICT" >"$TMPDIR/loadgen.log" 2>&1 &
LG_PID=$!

# wait for the supervisor to grow the fleet while the surge runs
GREW=""
for _ in $(seq 1 120); do
    if grep -q '"action": "scale_up"' "$AS_LOG" \
        && [[ "$(grep -c '"event": "replica_started"' "$AS_LOG")" -ge 2 ]]; then
        GREW=1; break
    fi
    kill -0 "$LG_PID" 2>/dev/null || break
    sleep 0.5
done
[[ -n "$GREW" ]] || {
    echo "FAIL: supervisor never scaled out under the surge"
    tail -60 "$AS_LOG"; kill "$LG_PID" 2>/dev/null || true; exit 1; }
PEAK="$(grep -c '"event": "replica_started"' "$AS_LOG")"
echo "PASS scale-out (scale_up decisions, $PEAK replicas started)"

# kill the newest replica NOW, mid-surge: the fleet is hot, so no
# drain-based retirement can race the victim — this death is
# unambiguously a crash the supervisor must repair, under live load
VICTIM_PID="$(grep '"event": "replica_started"' "$AS_LOG" | tail -1 \
    | python -c 'import json,sys; print(json.loads(sys.stdin.read())["pid"])')"
kill -9 "$VICTIM_PID" 2>/dev/null || {
    echo "FAIL: could not kill replica pid $VICTIM_PID"
    kill "$LG_PID" 2>/dev/null || true; exit 1; }
REPLACED=""
for _ in $(seq 1 120); do
    if grep -q '"event": "replicas_replaced"' "$AS_LOG"; then REPLACED=1; break; fi
    sleep 1
done
[[ -n "$REPLACED" ]] || {
    echo "FAIL: killed replica (pid $VICTIM_PID) never replaced"
    tail -60 "$AS_LOG"; kill "$LG_PID" 2>/dev/null || true; exit 1; }
grep -q '"event": "replica_died"' "$AS_LOG" || {
    echo "FAIL: replica death not reported as an event"; exit 1; }
grep '"event": "replicas_replaced"' "$AS_LOG" | tail -1 \
    | grep -q '"replaced": 0' && {
    echo "FAIL: death detected but replacement never came up"
    tail -60 "$AS_LOG"; exit 1; }
echo "PASS kill -9 mid-surge (died -> replaced under load)"

# the whole run — surge, death, replacement — must still verdict green
wait "$LG_PID" || {
    echo "FAIL: ramp loadgen verdict red"; cat "$TMPDIR/loadgen.log"; exit 1; }
grep -q '"passed": true' "$VERDICT" || {
    echo "FAIL: invariant verdict not green"; cat "$VERDICT"; exit 1; }
echo "PASS ramp verdict green (nothing lost, typed sheds only, kill absorbed)"

# the autoscaler's own series ride the router's federated /metrics
fetch "$ROUTER/metrics" | grep -q 'keystone_autoscale_decisions_total' || {
    echo "FAIL: keystone_autoscale_* series missing from /metrics"; exit 1; }
fetch "$ROUTER/metrics" \
    | grep 'keystone_autoscale_decisions_total' \
    | grep -q 'action="scale_up"' || {
    echo "FAIL: scale_up not counted on keystone_autoscale_decisions_total"; exit 1; }
fetch "$ROUTER/metrics" \
    | grep -q 'keystone_autoscale_replicas_replaced_total' || {
    echo "FAIL: replacement not counted on keystone_autoscale_replicas_replaced_total"; exit 1; }
echo "PASS keystone_autoscale_* exported"

# ---- c. load gone -> drain-based scale-down to baseline ------------------
echo "== idle: drain-based scale-down to the 1-replica baseline =="
BASELINE=""
for _ in $(seq 1 120); do
    if [[ "$(ready_replicas 2>/dev/null || echo 0)" == "1" ]] \
        && grep -q '"action": "scale_down"' "$AS_LOG"; then
        BASELINE=1; break
    fi
    sleep 1
done
[[ -n "$BASELINE" ]] || {
    echo "FAIL: fleet never drained back to 1 replica"
    fetch "$ROUTER/fleetz" || true; tail -60 "$AS_LOG"; exit 1; }
grep -q '"event": "replica_retired"' "$AS_LOG" || {
    echo "FAIL: scale-down did not retire gracefully (no replica_retired)"; exit 1; }
# retirement deregisters: the roster must hold exactly the survivors,
# not dead entries lingering until probes fail them
ROSTER="$(fetch "$ROUTER/fleetz" | python -c '
import json, sys; print(len(json.load(sys.stdin)["replicas"]))')"
[[ "$ROSTER" == "1" ]] || {
    echo "FAIL: roster still lists $ROSTER replicas after scale-down"
    fetch "$ROUTER/fleetz"; exit 1; }
echo "PASS scale-down (scale_down decisions, graceful retire, roster clean)"

# ---- graceful shutdown ----------------------------------------------------
kill "$AS_PID"
for _ in $(seq 1 30); do
    kill -0 "$AS_PID" 2>/dev/null || break
    sleep 1
done
kill -0 "$AS_PID" 2>/dev/null && {
    echo "FAIL: serve-autoscale did not exit on SIGTERM"; exit 1; }
AS_PID=""

echo "smoke-autoscale: all checks passed"
