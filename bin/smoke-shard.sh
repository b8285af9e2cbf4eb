#!/usr/bin/env bash
# Smoke-test mesh-sharded serving end to end:
#
#  1. a real `serve-gateway --shard-model` subprocess next to an
#     unsharded one over the SAME model: /predict answers match, the
#     sharded gateway's AOT store holds entries whose fingerprint meta
#     carries the `sharding_token` (a mesh-sharded program can never
#     collide with a replicated one), and the AOT counters are on
#     /metrics;
#  2. keystone-lint self-clean stays at 0 findings (the new
#     serving/sharding.py module included).
#
# Output parity of a sharded engine with a replicated one, and the
# over-one-device-budget model served sharded inside the budget, are
# held by tests/serving/test_sharding.py.
#
# CI-friendly: CPU backend with 8 virtual devices, ~1 min, no network
# beyond localhost.
#
#   bin/smoke-shard.sh
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
TMPDIR="$(mktemp -d)"
AOT_DIR="$TMPDIR/aot"
SHARD_LOG="$TMPDIR/shard.log"
PLAIN_LOG="$TMPDIR/plain.log"
DEV8="--xla_force_host_platform_device_count=8"
cleanup() {
    [[ -n "${SHARD_PID:-}" ]] && kill "$SHARD_PID" 2>/dev/null || true
    [[ -n "${PLAIN_PID:-}" ]] && kill "$PLAIN_PID" 2>/dev/null || true
    rm -rf "$TMPDIR"
}
trap cleanup EXIT

echo "== serve-gateway --shard-model vs unsharded parity drill =="
GWARGS=(--gateway-port 0 --buckets 4,8 --lanes 1 --d 64 --hidden 64 --depth 2)
XLA_FLAGS="$DEV8" JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    KEYSTONE_AOT_CACHE="$AOT_DIR" \
    python -m keystone_tpu serve-gateway "${GWARGS[@]}" --shard-model \
    >"$SHARD_LOG" 2>&1 &
SHARD_PID=$!
XLA_FLAGS="$DEV8" JAX_PLATFORMS=cpu PYTHONPATH="$ROOT" \
    python -m keystone_tpu serve-gateway "${GWARGS[@]}" --no-cache \
    >"$PLAIN_LOG" 2>&1 &
PLAIN_PID=$!

wait_for_base() {
    local log="$1" pid="$2" base=""
    for _ in $(seq 1 240); do
        base="$(python - "$log" <<'PY'
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
        [[ -n "$base" ]] && { echo "$base"; return 0; }
        kill -0 "$pid" 2>/dev/null || {
            echo "FAIL: gateway died before binding" >&2
            cat "$log" >&2; return 1; }
        sleep 0.5
    done
    echo "FAIL: no handshake after 120s" >&2; cat "$log" >&2; return 1
}
SHARD_BASE="$(wait_for_base "$SHARD_LOG" "$SHARD_PID")"
PLAIN_BASE="$(wait_for_base "$PLAIN_LOG" "$PLAIN_PID")"
echo "sharded gateway on $SHARD_BASE, unsharded on $PLAIN_BASE"

python - "$SHARD_BASE" "$PLAIN_BASE" <<'PY'
import json, sys, urllib.request
import numpy as np

shard, plain = sys.argv[1], sys.argv[2]
rng = np.random.default_rng(7)
inst = rng.standard_normal((64,)).astype(float).round(4).tolist()
def predict(base):
    req = urllib.request.Request(
        base + "/predict",
        data=json.dumps({"instances": [inst]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    return np.asarray(
        json.loads(urllib.request.urlopen(req, timeout=60).read())
        ["predictions"][0]
    )
a, b = predict(shard), predict(plain)
assert np.allclose(a, b, rtol=1e-4, atol=1e-5), (
    f"sharded /predict diverges: max abs diff {np.abs(a - b).max()}"
)
print(f"/predict parity OK (max abs diff {np.abs(a - b).max():.2e})")
PY
echo "PASS /predict parity (sharded vs unsharded)"

# the sharded gateway's AOT entries: counters scraped on /metrics and
# every stored fingerprint meta carrying the sharding_token
METRICS="$(python -c 'import sys, urllib.request; \
sys.stdout.write(urllib.request.urlopen(sys.argv[1], timeout=15).read().decode())' \
    "$SHARD_BASE/metrics")"
grep -q 'keystone_aot_cache_misses_total' <<<"$METRICS" || {
    echo "FAIL: /metrics missing keystone_aot_cache_* on the sharded gateway"
    grep keystone_aot <<<"$METRICS" || true
    exit 1; }
echo "PASS /metrics keystone_aot_cache_* present"

PYTHONPATH="$ROOT" python - "$AOT_DIR" <<'PY'
import sys
from keystone_tpu.serving.aot import AotStore
from keystone_tpu.observability.registry import MetricsRegistry

store = AotStore(sys.argv[1], registry=MetricsRegistry())
entries = store.entries()
assert entries, "sharded gateway saved no AOT entries"
for key in entries:
    meta = store.read_meta(key)
    assert meta is not None, f"unreadable entry {key}"
    assert meta.get("sharding_token"), (
        f"entry {key} meta lacks the sharding_token: {sorted(meta)}"
    )
print(f"{len(entries)} AOT entries, every meta pins a sharding_token")
PY
echo "PASS sharded AOT entries fingerprinted with sharding_token"

kill "$SHARD_PID" "$PLAIN_PID" 2>/dev/null || true
wait "$SHARD_PID" 2>/dev/null || true
wait "$PLAIN_PID" 2>/dev/null || true
SHARD_PID=""; PLAIN_PID=""

echo "== keystone-lint self-clean =="
PYTHONPATH="$ROOT" python -m keystone_tpu keystone-lint
echo "PASS keystone-lint 0 findings"

echo "smoke-shard: all checks passed"
