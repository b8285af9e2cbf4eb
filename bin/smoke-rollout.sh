#!/usr/bin/env bash
# Smoke-test the online model lifecycle end to end, both directions:
#
#  1. a live `serve-gateway --refit` subprocess fed by a real
#     `serve-loadgen` run that labels a fraction of its own traffic
#     with the synthetic teacher and POSTs it to /feedback: the
#     controller must walk idle -> shadow -> canary -> promoted on
#     /lifecyclez, the loadgen invariant verdict must stay green, and
#     the keystone_lifecycle_* families must show up on /metrics;
#  2. same live gateway, `lifecycle.refit.poison` armed over /chaosz:
#     the next refit cycle's candidate must be caught by the accuracy
#     gate and auto-rolled back (reason on /lifecyclez, counted on
#     keystone_lifecycle_rollbacks_total) while the loadgen verdict
#     stays green — served traffic never notices;
#  3. the request log round-trips through the loadgen trace parser
#     (model-tagged lines included), and keystone-lint stays at 0
#     findings.
#
# The in-process loop (promotion under load with no failed request, a
# poisoned refit rolled back) is tests/lifecycle/test_controller.py's.
#
# CI-friendly: CPU backend, ~2-3 min, no network beyond localhost.
#
#   bin/smoke-rollout.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
SERVER_LOG="$TMPDIR/server.log"
REQ_LOG="$TMPDIR/requests.jsonl"
cleanup() {
    [[ -n "${SERVER_PID:-}" ]] && kill "$SERVER_PID" 2>/dev/null || true
    [[ -n "${LOADGEN_PID:-}" ]] && kill "$LOADGEN_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

echo "== live serve-gateway --refit + loadgen feedback drill =="
D=24 HIDDEN=32 DEPTH=3 HEAD_SEED=7
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway --gateway-port 0 \
    --refit --d $D --hidden $HIDDEN --depth $DEPTH \
    --buckets 4,8 --refit-interval-s 0.5 --refit-min-samples 128 \
    --canary-fraction 0.25 --request-log "$REQ_LOG" \
    >"$SERVER_LOG" 2>&1 &
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
        echo "FAIL: refit gateway died before binding"; cat "$SERVER_LOG"; exit 1; }
    sleep 0.5
done
[[ -n "$BASE" ]] || { echo "FAIL: no handshake after 120s"; cat "$SERVER_LOG"; exit 1; }
echo "refit gateway up on $BASE"

# the lifecycle surface exists and starts idle
python - "$BASE" <<'PY'
import json, sys, urllib.request
doc = json.loads(urllib.request.urlopen(
    sys.argv[1] + "/lifecyclez", timeout=15).read())
st = doc["models"]["default"]
assert st["state"] == "idle", st
assert st["version"] == 0, st
print(f"/lifecyclez OK: default model idle at v0")
PY

# labeled open-loop traffic: half the issued payloads also go to
# /feedback, labeled by the teacher whose HEAD differs from the
# served (now stale) model — the refit must learn the new head
JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-loadgen --target "$BASE" --d $D \
    --synthetic 4000 --rate 150 --seed 1 \
    --feedback-fraction 0.5 \
    --teacher "hidden=$HIDDEN,depth=$DEPTH,head_seed=$HEAD_SEED" \
    --report "$TMPDIR/loadgen-promote.json" \
    >"$TMPDIR/loadgen-promote.log" 2>&1 &
LOADGEN_PID=$!

# watch the walk: the feedback stream keeps flowing, so the
# controller may start MORE candidate cycles after the first
# promotion — sample until the monotonic promotions counter moves and
# capture THAT status (v1 vs the genuinely stale incumbent)
PROMOTED=""
for _ in $(seq 1 240); do
    PROMOTED="$(python - "$BASE" "$TMPDIR/promoted.json" <<'PY'
import json, sys, urllib.request
try:
    doc = json.loads(urllib.request.urlopen(
        sys.argv[1] + "/lifecyclez", timeout=15).read())
    st = doc["models"]["default"]
    if st["promotions"] >= 1:
        with open(sys.argv[2], "w") as f:
            json.dump(st, f)
        print("yes")
except Exception:
    pass
PY
)"
    [[ "$PROMOTED" == "yes" ]] && break
    kill -0 "$LOADGEN_PID" 2>/dev/null || break
    sleep 0.5
done

wait "$LOADGEN_PID" && LOADGEN_RC=0 || LOADGEN_RC=$?
LOADGEN_PID=""
[[ "$LOADGEN_RC" == 0 ]] || {
    echo "FAIL: promote-phase loadgen verdict went red (rc=$LOADGEN_RC)"
    cat "$TMPDIR/loadgen-promote.log"; exit 1; }
grep -q '"feedback"' "$TMPDIR/loadgen-promote.log" || {
    echo "FAIL: loadgen never reported its feedback counters"
    cat "$TMPDIR/loadgen-promote.log"; exit 1; }
[[ "$PROMOTED" == "yes" ]] || {
    echo "FAIL: no promotion observed on /lifecyclez"
    python -c 'import sys, urllib.request; \
print(urllib.request.urlopen(sys.argv[1] + "/lifecyclez", timeout=15).read().decode())' \
        "$BASE" || true
    exit 1; }

python - "$TMPDIR/promoted.json" <<'PY'
import json, sys
st = json.load(open(sys.argv[1]))
assert st["version"] >= 1, st
assert st["promotions"] >= 1, st
errs = st["errors"]
assert errs["candidate"] is not None and errs["incumbent"] is not None, st
assert errs["candidate"] < errs["incumbent"], (
    f"promoted candidate must beat the stale incumbent on held-out "
    f"labels: {errs}")
print(
    f"promotion OK: v{st['version']} promoted, held-out err "
    f"{errs['candidate']} vs stale {errs['incumbent']}"
)
PY
echo "PASS live refit -> shadow -> canary -> promoted (green verdict)"

METRICS="$(python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=15).read().decode())' \
    "$BASE/metrics")"
for fam in \
    keystone_lifecycle_state \
    keystone_lifecycle_version \
    keystone_lifecycle_refit_samples_total \
    keystone_lifecycle_shadow_pairs_total \
    keystone_lifecycle_canary_requests_total \
    keystone_lifecycle_promotions_total; do
    grep -q "^$fam" <<<"$METRICS" || {
        echo "FAIL: /metrics missing $fam family:"
        grep keystone_lifecycle <<<"$METRICS" || true
        exit 1; }
done
echo "PASS /metrics keystone_lifecycle_* families"

echo "== poisoned refit: auto-rollback drill =="
# arm the poison over the chaos surface; the NEXT refit cycle's
# accumulated chunks are corrupted (the holdout stays clean), so the
# accuracy gate must catch the candidate in shadow and roll back
python - "$BASE" <<'PY'
import json, sys, urllib.request
req = urllib.request.Request(
    sys.argv[1] + "/chaosz",
    data=json.dumps(
        {"arm": {"point": "lifecycle.refit.poison", "count": 16}}
    ).encode(),
    headers={"Content-Type": "application/json"},
)
body = json.loads(urllib.request.urlopen(req, timeout=15).read())
print(f"armed: {body}")
PY

JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-loadgen --target "$BASE" --d $D \
    --synthetic 2500 --rate 150 --seed 2 \
    --feedback-fraction 0.5 \
    --teacher "hidden=$HIDDEN,depth=$DEPTH,head_seed=$HEAD_SEED" \
    --report "$TMPDIR/loadgen-poison.json" \
    >"$TMPDIR/loadgen-poison.log" 2>&1 &
LOADGEN_PID=$!

# the rollback needs TICKS, not traffic: a poisoned candidate solved
# from the tail of the feedback stream is caught by the accuracy gate
# on the next 0.5s tick even after the loadgen exits — so keep
# polling through a grace window once the traffic stops
ROLLED=""
GRACE=0
for _ in $(seq 1 240); do
    ROLLED="$(python - "$BASE" <<'PY'
import re, sys, urllib.request
try:
    text = urllib.request.urlopen(
        sys.argv[1] + "/metrics", timeout=15).read().decode()
    total = sum(
        float(m.group(1)) for m in re.finditer(
            r"^keystone_lifecycle_rollbacks_total\{[^}]*\} (\S+)",
            text, re.M)
    )
    if total >= 1:
        print("yes")
except Exception:
    pass
PY
)"
    [[ "$ROLLED" == "yes" ]] && break
    if ! kill -0 "$LOADGEN_PID" 2>/dev/null; then
        GRACE=$((GRACE + 1))
        [[ "$GRACE" -ge 60 ]] && break
    fi
    sleep 0.5
done
[[ "$ROLLED" == "yes" ]] || {
    echo "FAIL: poisoned refit never rolled back"
    python -c 'import sys, urllib.request; \
print(urllib.request.urlopen(sys.argv[1] + "/lifecyclez", timeout=15).read().decode())' \
        "$BASE" || true
    exit 1; }

wait "$LOADGEN_PID" && LOADGEN_RC=0 || LOADGEN_RC=$?
LOADGEN_PID=""
[[ "$LOADGEN_RC" == 0 ]] || {
    echo "FAIL: poison-phase loadgen verdict went red (rc=$LOADGEN_RC) "
    echo "— served traffic must never notice a rolled-back candidate"
    cat "$TMPDIR/loadgen-poison.log"; exit 1; }

# the rollback is visible, attributed, and serving still answers
python - "$BASE" "$D" <<'PY'
import json, re, sys, urllib.request
base, d = sys.argv[1], int(sys.argv[2])
text = urllib.request.urlopen(base + "/metrics", timeout=15).read().decode()
rb = {
    m.group(0): float(m.group(1)) for m in re.finditer(
        r"^keystone_lifecycle_rollbacks_total\{[^}]*\} (\S+)", text, re.M)
}
assert rb and sum(rb.values()) >= 1, rb
assert any("accuracy" in k or "shadow_diff" in k for k in rb), rb
fired = [
    l for l in text.splitlines()
    if l.startswith("keystone_fault_injections_total")
    and "lifecycle.refit.poison" in l
]
assert fired, "the poison never counted on keystone_fault_injections_total"
req = urllib.request.Request(
    base + "/predict",
    data=json.dumps({"instances": [[0.1] * d]}).encode(),
    headers={"Content-Type": "application/json"},
)
body = json.loads(urllib.request.urlopen(req, timeout=60).read())
assert len(body["predictions"]) == 1, body
print(f"rollback OK: {rb}; poison audited: {fired[0]}; serving answers")
PY
echo "PASS poisoned refit -> auto-rollback (green verdict, serving up)"

kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""

echo "== request-log round trip through the trace parser =="
PYTHONPATH="$ROOT" python - "$REQ_LOG" <<'PY'
import sys
from keystone_tpu.loadgen import trace
with open(sys.argv[1]) as f:
    events = trace.parse_request_log(f)
assert events, "request log parsed to zero events"
posts = trace.normalize(trace.collapse_posts(events))
assert posts and posts[0].ts == 0.0, posts[:3]
models = {e.model for e in events}
print(f"round trip OK: {len(events)} lines -> {len(posts)} POSTs, "
      f"models seen: {sorted(models, key=str)}")
PY
echo "PASS request-log round trip"

echo "== keystone-lint self-clean =="
PYTHONPATH="$ROOT" python -m keystone_tpu keystone-lint
echo "PASS keystone-lint 0 findings"

echo "smoke-rollout: all checks passed"
