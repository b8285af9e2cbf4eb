"""The open-loop generator: gap preservation with speed scaling,
response-independence (open loop, not closed), the chaos timeline,
and outcome classification through both targets."""

import threading
import time

import pytest

from keystone_tpu.loadgen.runner import (
    FaultPlan,
    InprocTarget,
    LoadGenerator,
    LoadReport,
    RequestRecord,
)
from keystone_tpu.loadgen.trace import TraceEvent


class StubTarget:
    """Records issue times; responds after ``service_s``."""

    def __init__(self, service_s=0.0):
        self.service_s = service_s
        self.issued = []
        self.armed = []
        self.disarmed = []
        self._lock = threading.Lock()

    def send(self, event):
        with self._lock:
            self.issued.append(time.perf_counter())
        if self.service_s:
            time.sleep(self.service_s)
        return RequestRecord(
            0, 0.0, 0.0, "ok", n_rows=event.n_rows,
            latency_s=self.service_s,
        )

    def ready(self):
        return True

    def arm_fault(self, spec):
        self.armed.append((time.perf_counter(), dict(spec)))

    def disarm_fault(self, point):
        self.disarmed.append(point)


def _events(gaps):
    ts, out = 0.0, []
    for g in gaps:
        ts += g
        out.append(TraceEvent(ts=ts))
    return out


def test_replay_preserves_gaps():
    target = StubTarget()
    events = _events([0.0, 0.15, 0.15])
    LoadGenerator(target).run(events)
    gaps = [
        b - a for a, b in zip(target.issued, target.issued[1:])
    ]
    assert gaps[0] == pytest.approx(0.15, abs=0.05)
    assert gaps[1] == pytest.approx(0.15, abs=0.05)


def test_speed_scales_the_clock():
    target = StubTarget()
    events = _events([0.0, 0.2, 0.2])
    LoadGenerator(target).run(events, speed=4.0)
    gaps = [
        b - a for a, b in zip(target.issued, target.issued[1:])
    ]
    assert gaps[0] == pytest.approx(0.05, abs=0.04)
    assert gaps[1] == pytest.approx(0.05, abs=0.04)


def test_open_loop_issues_do_not_wait_for_responses():
    """A 300 ms server must not stretch a 3 x 30 ms arrival schedule:
    issue times follow the generator's clock, not the responses."""
    target = StubTarget(service_s=0.3)
    events = _events([0.0, 0.03, 0.03])
    report = LoadGenerator(target).run(events)
    assert len(target.issued) == 3
    span = target.issued[-1] - target.issued[0]
    assert span < 0.25, (
        f"arrivals took {span:.3f}s — the generator went closed-loop"
    )
    assert report.by_status() == {"ok": 3}
    # and every record still resolved with its latency
    assert all(r.latency_s for r in report.records)


def test_records_carry_schedule_lag():
    target = StubTarget()
    report = LoadGenerator(target).run(_events([0.0, 0.01]))
    for rec in report.records:
        assert rec.behind_s >= 0.0
        assert rec.t_send >= rec.t_sched


def test_fault_timeline_arms_mid_run_and_clears_at_end():
    target = StubTarget()
    events = _events([0.0] + [0.02] * 9)  # ~0.18s of arrivals
    plan = FaultPlan(
        spec={"point": "x.y", "delay_ms": 1}, at_s=0.1, for_s=5.0,
    )
    report = LoadGenerator(target).run(
        events, faults=[plan], recovery_probe_s=0.5
    )
    assert len(target.armed) == 1
    t_arm, spec = target.armed[0]
    assert spec["point"] == "x.y"
    assert spec["for_s"] == 5.0  # the self-disarm rides the spec
    # armed ~0.1s in, not at the start
    assert t_arm - target.issued[0] == pytest.approx(0.1, abs=0.06)
    # for_s outlived the run: the runner disarmed it explicitly and
    # stamped the actual clear time
    assert target.disarmed == ["x.y"]
    w = report.fault_windows[0]
    assert w.t_clear is not None and w.t_clear <= report.duration_s
    # target was ready: recovery measured
    assert report.ready_probed
    assert report.ready_recovery_s is not None


def test_fault_window_t_clear_honors_spec_level_for_s():
    """A duration given INSIDE the spec clause (for_s:N) must stamp
    the window's clear time just like FaultPlan.for_s — otherwise the
    recovery invariants measure against the wrong window."""
    target = StubTarget()
    events = _events([0.0, 0.02])
    plan = FaultPlan(
        spec={"point": "x.y", "for_s": 0.05}, at_s=0.0, for_s=None,
    )
    report = LoadGenerator(target).run(
        events, faults=[plan], recovery_probe_s=0.2, settle_s=0.1
    )
    w = report.fault_windows[0]
    assert w.t_clear == pytest.approx(w.t_arm + 0.05, abs=0.001)
    # the server self-disarms; the driver must NOT disarm again after
    # the window already closed on its own
    assert target.disarmed == []


def test_fault_at_waits_through_a_sparse_gap():
    """A plan must arm at ITS instant, not at the head of a long
    inter-arrival gap — arming early would let for_s expire the fault
    before any request ever meets it."""
    target = StubTarget()
    events = _events([0.0, 0.6])
    plan = FaultPlan(spec={"point": "x.y"}, at_s=0.3, for_s=0.1)
    LoadGenerator(target).run(
        events, faults=[plan], recovery_probe_s=0.2
    )
    t_arm, _ = target.armed[0]
    assert t_arm - target.issued[0] == pytest.approx(0.3, abs=0.08)


def test_report_stats_shape():
    target = StubTarget()
    report = LoadGenerator(target).run(_events([0.0, 0.01, 0.01]))
    stats = report.stats()
    assert stats["issued"] == 3
    assert stats["resolved"] == 3
    assert stats["lost"] == 0
    assert stats["untyped_failures"] == 0
    assert stats["shed_rate"] == 0.0
    assert stats["duration_s"] > 0


def test_p99_windows_select_by_send_time():
    report = LoadReport()
    for t, lat in [(0.0, 0.010), (1.0, 0.020), (2.0, 0.500)]:
        report.add(RequestRecord(0, t, t, "ok", latency_s=lat))
    assert report.p99(0.0, 2.0) == pytest.approx(0.02, rel=0.01)
    assert report.p99(2.0) == pytest.approx(0.5)
    assert report.p99(5.0) is None


# -- the in-process target classifies real gateway outcomes ----------------


def test_inproc_target_classifies_shed_and_ok(fitted):
    import jax.numpy as jnp

    from keystone_tpu.gateway import Gateway

    from gateway_fixtures import D

    gw = Gateway(
        fitted, buckets=(4, 8), n_lanes=1, max_delay_ms=1.0,
        warmup_example=jnp.zeros(D, jnp.float32),
        name="runner-inproc",
    )
    try:
        target = InprocTarget(gw, default_shape=(D,))
        ok = target.send(TraceEvent(ts=0.0, n_rows=2, shape=(D,)))
        assert ok.status == "ok" and not ok.untyped
        assert ok.latency_s is not None
    finally:
        gw.close()
    # a draining gateway sheds typed ("closed") — not an untyped error
    shed = target.send(TraceEvent(ts=0.0, n_rows=1, shape=(D,)))
    assert shed.status == "shed"
    assert shed.reason == "closed"
    assert not shed.untyped


def test_inproc_target_untyped_error_is_flagged(fitted):
    """An engine fault that escapes the retry plane must classify as
    an UNTYPED failure — the thing the invariant checker exists to
    catch. One lane + a dispatch error on it = no retry lane, the
    fault reaches the caller."""
    import jax.numpy as jnp

    from keystone_tpu.gateway import Gateway
    from keystone_tpu.loadgen import faults

    from gateway_fixtures import D

    with Gateway(
        fitted, buckets=(4, 8), n_lanes=1, max_delay_ms=1.0,
        warmup_example=jnp.zeros(D, jnp.float32),
        name="runner-untyped",
    ) as gw:
        target = InprocTarget(gw, default_shape=(D,))
        faults.arm("engine.dispatch.error")
        try:
            rec = target.send(TraceEvent(ts=0.0, n_rows=1, shape=(D,)))
        finally:
            faults.disarm_all()
        assert rec.status == "error"
        assert rec.untyped
        assert "FaultInjected" in rec.reason


@pytest.mark.parametrize(
    "spec",
    [
        {"point": "gateway.lane.kill", "match": {"lane": 0}},
        {"point": "pipeline.host_prep.stall", "delay_ms": 40.0},
    ],
    ids=["lane_kill", "prep_stall"],
)
def test_fault_mid_run_loses_nothing_and_fails_only_typed(fitted, spec):
    """Open-loop load through a two-lane pipelined gateway with a fault
    armed mid-run — one lane killed, or the host-prep stage stalled: the
    fault fires, every issued request resolves, whatever failed was shed
    typed, and readiness is back once the fault clears. The fault is
    bounded by its own ``count`` of fires, not by a clock: it is armed
    before the first arrival scheduled past ``at_s`` and stays armed
    until it has fired, however slowly a loaded machine gets there. The
    verdict's p99-recovery ratio is a timing and is not read here."""
    import jax.numpy as jnp

    from keystone_tpu.gateway import Gateway
    from keystone_tpu.loadgen import faults, synthesize
    from keystone_tpu.loadgen.invariants import InvariantChecker

    from gateway_fixtures import D

    point = spec["point"]
    fired_before = faults.get_injector().fired_count(point)
    events = synthesize(
        120, arrivals="poisson", rate=150.0, shape=(D,), seed=11
    )
    with Gateway(
        fitted, buckets=(4, 8), n_lanes=2, max_delay_ms=2.0,
        pipeline_depth=2, warmup_example=jnp.zeros(D, jnp.float32),
        name=f"runner-chaos-{point}",
    ) as gw:
        report = LoadGenerator(InprocTarget(gw, default_shape=(D,))).run(
            events,
            faults=[FaultPlan(spec={**spec, "count": 4}, at_s=0.2)],
            settle_s=0.3,
            recovery_probe_s=10.0,
        )
    assert faults.get_injector().fired_count(point) > fired_before
    assert report.issued == len(report.records) == 120
    verdict = InvariantChecker(max_shed_rate=0.9).check(report)
    held = {r.name: r for r in verdict.invariants}
    for name in (
        "every_admitted_request_resolves",
        "failures_are_typed_sheds_only",
        "readiness_recovers_after_fault",
        "shed_rate_bounded",
    ):
        assert held[name].passed, held[name].detail


class _FakeResponse:
    def read(self):
        return b"{}"

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def test_feedback_sender_samples_and_posts(monkeypatch):
    import urllib.request

    import numpy as np

    from keystone_tpu.loadgen import runner

    posted = []

    def fake_urlopen(req, timeout=None):
        posted.append(req)
        return _FakeResponse()

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    sender = runner.FeedbackSender(
        "http://example.invalid",
        labeler=lambda xs: np.zeros_like(xs),
        fraction=0.25,
        max_queue=256,
    )
    for _ in range(100):
        sender.offer(np.ones((2, 4), np.float32))
    stats = sender.close()
    # deterministic integer-part sampling: exactly fraction of offers
    assert len(posted) == 25
    assert stats["sent"] == 25 * 2  # rows, not requests
    assert stats["dropped"] == 0
    assert stats["errors"] == 0
    assert all(r.full_url.endswith("/feedback") for r in posted)


def test_feedback_sender_errors_never_block(monkeypatch):
    import urllib.request

    import numpy as np

    from keystone_tpu.loadgen import runner

    def exploding_urlopen(req, timeout=None):
        raise OSError("connection refused")

    monkeypatch.setattr(urllib.request, "urlopen", exploding_urlopen)
    sender = runner.FeedbackSender(
        "http://example.invalid",
        labeler=lambda xs: np.zeros_like(xs),
        fraction=1.0,
    )
    for _ in range(5):
        sender.offer(np.ones((1, 4), np.float32))
    stats = sender.close()
    assert stats["errors"] == 5
    assert stats["sent"] == 0


def test_feedback_sender_fraction_validation():
    from keystone_tpu.loadgen.runner import FeedbackSender

    with pytest.raises(ValueError):
        FeedbackSender("http://x", labeler=lambda xs: xs, fraction=1.5)
