"""On-demand profiling (observability/profilez.py): parameter
validation, the one-capture-at-a-time 409 contract, and the e2e
round-trip on ephemeral admin + gateway ports."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from keystone_tpu.observability import AdminServer, MetricsRegistry, Tracer
from keystone_tpu.observability import profilez


def test_bad_seconds_is_400():
    code, doc = profilez.profilez_document("not-a-number")
    assert code == 400 and doc["error"] == "bad_request"
    code, _ = profilez.profilez_document("0")
    assert code == 400
    code, _ = profilez.profilez_document("-2")
    assert code == 400
    code, _ = profilez.profilez_document(
        str(profilez.MAX_CAPTURE_SECONDS + 1)
    )
    assert code == 400


def test_capture_writes_trace_files(tmp_path):
    code, doc = profilez.profilez_document("0.2", base_dir=str(tmp_path))
    assert code == 200, doc
    assert doc["trace_dir"].startswith(str(tmp_path))
    assert doc["file_count"] >= 1, doc
    assert doc["captured_s"] >= 0.2


def test_capture_retention_is_bounded(tmp_path):
    """Only the newest MAX_RETAINED_CAPTURES dirs survive: a probe
    hitting /profilez periodically must not fill the disk."""
    import os
    import time as time_mod

    for i in range(4):
        d = tmp_path / f"trace-2026-{i}"
        d.mkdir()
        (d / "plane.pb").write_bytes(b"x")
        # distinct mtimes so newest-wins ordering is deterministic
        stamp = time_mod.time() - (4 - i) * 10
        os.utime(d, (stamp, stamp))
    (tmp_path / "unrelated").mkdir()  # non-capture dirs untouched
    profilez._prune_captures(str(tmp_path), keep=2)
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert kept == ["trace-2026-2", "trace-2026-3", "unrelated"]
    # the live endpoint prunes as it captures: the newest capture is
    # always retained
    code, doc = profilez.profilez_document("0.1", base_dir=str(tmp_path))
    assert code == 200
    assert doc["trace_dir"] in [str(p) for p in tmp_path.iterdir()]


def test_dead_process_dirs_are_swept(tmp_path):
    import os

    mine = tmp_path / f"keystone-profilez-{os.getpid()}"
    dead = tmp_path / "keystone-profilez-999999999"  # no such pid
    alive = tmp_path / f"keystone-profilez-{os.getppid()}"
    other = tmp_path / "keystone-profilez-notapid"
    for d in (mine, dead, alive, other):
        d.mkdir()
    profilez._sweep_dead_process_dirs(str(mine))
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert dead.name not in kept  # dead pid's captures reclaimed
    assert mine.name in kept and alive.name in kept
    assert other.name in kept  # unparseable names left alone


def test_concurrent_capture_is_409(tmp_path):
    """jax.profiler allows one trace per process: while a capture
    holds the lock, a second request must get a typed 409, and the
    lock must release afterwards."""
    with profilez._capture_lock:
        code, doc = profilez.profilez_document("0.1")
        assert code == 409
        assert doc["error"] == "capture_in_progress"
    # lock released: capture works again
    code, _ = profilez.profilez_document("0.1", base_dir=str(tmp_path))
    assert code == 200


def test_profilez_e2e_on_admin_and_gateway_ports(tmp_path):
    """The acceptance drill: GET /profilez?seconds=N on an ephemeral
    admin port returns a capture while the concurrent second request
    409s; the gateway port mirrors the route."""
    with AdminServer(registry=MetricsRegistry(), tracer=Tracer()) as srv:
        results = []

        def hit(seconds):
            try:
                with urllib.request.urlopen(
                    srv.url(f"/profilez?seconds={seconds}"), timeout=30
                ) as resp:
                    results.append((resp.status, json.loads(resp.read())))
            except urllib.error.HTTPError as e:
                results.append((e.code, json.loads(e.read())))

        t1 = threading.Thread(target=hit, args=(1.0,))
        t2 = threading.Thread(target=hit, args=(1.0,))
        t1.start()
        t2.start()
        t1.join()
        t2.join()
        codes = sorted(c for c, _ in results)
        assert codes == [200, 409], results
        ok = next(doc for c, doc in results if c == 200)
        assert ok["file_count"] >= 1
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                srv.url("/profilez?seconds=oops"), timeout=10
            )
        assert e.value.code == 400


def test_profilez_route_on_gateway_port():
    from keystone_tpu.gateway import Gateway, GatewayServer
    from keystone_tpu.serving.demo_model import build_pipeline

    import numpy as np

    fitted = build_pipeline(d=8, hidden=8, depth=2)
    with Gateway(
        fitted, buckets=(4,), n_lanes=1,
        warmup_example=np.zeros((8,), np.float32),
        registry=MetricsRegistry(), name="pz-gw",
    ) as gw:
        with GatewayServer(gw, port=0, registry=MetricsRegistry()) as srv:
            with urllib.request.urlopen(
                srv.url("/profilez?seconds=0.2"), timeout=30
            ) as resp:
                doc = json.loads(resp.read())
            assert resp.status == 200
            assert doc["file_count"] >= 1
