"""The invariant checker must be able to FAIL: a stub gateway that
loses a future, returns an untyped 500, or never recovers readiness
must each produce a red verdict — otherwise the green verdicts the
tests and smoke scripts assert are worthless."""

import pytest

from keystone_tpu.loadgen.invariants import InvariantChecker, Verdict
from keystone_tpu.loadgen.runner import (
    FaultWindow,
    LoadGenerator,
    LoadReport,
    RequestRecord,
)
from keystone_tpu.loadgen.trace import TraceEvent


def _report(
    records,
    fault=None,
    issued=None,
    ready_recovery_s="unset",
    probed=True,
):
    rep = LoadReport()
    for r in records:
        rep.add(r)
    rep.issued = issued if issued is not None else len(records)
    rep.duration_s = max((r.t_send for r in records), default=0.0) + 1.0
    if fault is not None:
        rep.fault_windows.append(fault)
        rep.ready_probed = probed
        rep.ready_recovery_s = (
            1.0 if ready_recovery_s == "unset" else ready_recovery_s
        )
    return rep


def _ok(t, lat=0.01):
    return RequestRecord(0, t, t, "ok", latency_s=lat)


def _steady(n=60, lat=0.01, t0=0.0, dt=0.1):
    return [_ok(t0 + i * dt, lat) for i in range(n)]


def _fault(t_arm=2.0, t_clear=3.0):
    return FaultWindow(point="gateway.lane.kill", t_arm=t_arm,
                       t_clear=t_clear)


def _failed_names(verdict):
    return {r.name for r in verdict.failures()}


def test_clean_report_is_green():
    v = InvariantChecker().check(_report(_steady(), fault=_fault()))
    assert v.passed, v.to_json()
    assert isinstance(v, Verdict)
    assert v.stats["pre_fault_p99_ms"] is not None


def test_lost_future_fails_resolution_invariant():
    records = _steady() + [
        RequestRecord(0, 1.0, 1.0, "lost", reason="hung 30s")
    ]
    v = InvariantChecker().check(_report(records, fault=_fault()))
    assert not v.passed
    assert "every_admitted_request_resolves" in _failed_names(v)


def test_vanished_request_fails_resolution_invariant():
    # issued 61, only 60 records came back: a request with NO record
    # (the stub gateway swallowed the future entirely)
    v = InvariantChecker().check(
        _report(_steady(), fault=_fault(), issued=61)
    )
    assert not v.passed
    assert "every_admitted_request_resolves" in _failed_names(v)
    detail = [
        r for r in v.invariants
        if r.name == "every_admitted_request_resolves"
    ][0].detail
    assert "vanished" in detail


def test_untyped_500_fails_typed_only_invariant():
    records = _steady() + [
        RequestRecord(
            0, 1.0, 1.0, "error", code=500,
            reason="internal", untyped=True,
        )
    ]
    v = InvariantChecker().check(_report(records, fault=_fault()))
    assert not v.passed
    assert "failures_are_typed_sheds_only" in _failed_names(v)


def test_typed_sheds_do_not_fail_typed_only():
    records = _steady() + [
        RequestRecord(
            0, 1.0, 1.0, "shed", code=429, reason="queue_full",
        )
    ]
    v = InvariantChecker().check(_report(records, fault=_fault()))
    assert "failures_are_typed_sheds_only" not in _failed_names(v)


def test_never_recovered_readiness_fails():
    v = InvariantChecker().check(
        _report(_steady(), fault=_fault(), ready_recovery_s=None)
    )
    assert not v.passed
    assert "readiness_recovers_after_fault" in _failed_names(v)


def test_unprobed_readiness_with_faults_fails():
    # fault windows ran but nobody probed /readyz: the invariant must
    # refuse to pass on missing evidence
    v = InvariantChecker().check(
        _report(_steady(), fault=_fault(), probed=False,
                ready_recovery_s=None)
    )
    assert "readiness_recovers_after_fault" in _failed_names(v)


def test_p99_that_never_recovers_fails():
    # pre-fault 10ms; everything after the fault is 200ms forever
    records = _steady(n=30, lat=0.01)  # t in [0, 3)
    records += [_ok(3.0 + i * 0.1, 0.2) for i in range(150)]
    v = InvariantChecker(recovery_within_s=5.0).check(
        _report(records, fault=_fault(t_arm=2.5, t_clear=3.0))
    )
    assert not v.passed
    assert "p99_recovers_after_fault" in _failed_names(v)


def test_p99_recovery_slides_past_the_drain_transient():
    # 2s of 300ms drain right after the fault clears, then healthy:
    # the sliding window finds the recovery; whole-post-window p99
    # alone would have failed it
    records = _steady(n=30, lat=0.01)
    records += [_ok(3.0 + i * 0.1, 0.3) for i in range(20)]   # drain
    records += [_ok(5.0 + i * 0.1, 0.01) for i in range(100)]  # healthy
    v = InvariantChecker(recovery_within_s=10.0).check(
        _report(records, fault=_fault(t_arm=2.5, t_clear=3.0))
    )
    assert "p99_recovers_after_fault" not in _failed_names(v)
    assert v.stats["p99_recovery_s"] is not None
    assert v.stats["recovered_p99_ms"] < 50


def test_no_pre_fault_traffic_fails_rather_than_guesses():
    records = [_ok(3.0 + i * 0.1) for i in range(50)]
    v = InvariantChecker().check(
        _report(records, fault=_fault(t_arm=0.0, t_clear=1.0))
    )
    assert "p99_recovers_after_fault" in _failed_names(v)


def test_no_faults_skips_chaos_invariants():
    v = InvariantChecker().check(_report(_steady()))
    names = {r.name for r in v.invariants}
    assert "p99_recovers_after_fault" not in names
    assert "readiness_recovers_after_fault" not in names
    assert v.passed


def test_shed_rate_bound():
    records = _steady(n=50) + [
        RequestRecord(0, 1.0, 1.0, "shed", reason="queue_full")
        for _ in range(50)
    ]
    red = InvariantChecker(max_shed_rate=0.25).check(_report(records))
    assert "shed_rate_bounded" in _failed_names(red)
    green = InvariantChecker(max_shed_rate=0.6).check(_report(records))
    assert green.passed


def test_absolute_p99_bound():
    v = InvariantChecker(max_p99_s=0.005).check(
        _report(_steady(lat=0.02))
    )
    assert "p99_bounded" in _failed_names(v)


def test_verdict_json_round_trip():
    import json

    v = InvariantChecker().check(_report(_steady()))
    doc = json.loads(v.to_json())
    assert doc["passed"] is True
    assert {r["name"] for r in doc["invariants"]} == {
        "every_admitted_request_resolves",
        "failures_are_typed_sheds_only",
    }


# -- end to end: a stub gateway whose bugs the checker must catch ----------


class _LosingTarget:
    """A 'gateway' that silently never answers one request in ten and
    500s another — the checker is the only line of defense."""

    def __init__(self):
        self.n = 0

    def send(self, event):
        self.n += 1
        if self.n % 10 == 0:
            return RequestRecord(
                0, 0.0, 0.0, "lost", reason="future never resolved"
            )
        if self.n % 10 == 5:
            return RequestRecord(
                0, 0.0, 0.0, "error", code=500,
                reason="internal", untyped=True,
            )
        return RequestRecord(0, 0.0, 0.0, "ok", latency_s=0.001)

    def ready(self):
        return False  # and it never comes back

    def arm_fault(self, spec):
        pass

    def disarm_fault(self, point):
        pass


def test_checker_catches_a_lying_stub_gateway_end_to_end():
    from keystone_tpu.loadgen.runner import FaultPlan

    events = [TraceEvent(ts=i * 0.005) for i in range(30)]
    gen = LoadGenerator(_LosingTarget())
    report = gen.run(
        events,
        faults=[FaultPlan(
            spec={"point": "gateway.lane.kill"}, at_s=0.05, for_s=0.05,
        )],
        recovery_probe_s=0.3,
    )
    v = InvariantChecker().check(report)
    assert not v.passed
    failed = _failed_names(v)
    assert "every_admitted_request_resolves" in failed
    assert "failures_are_typed_sheds_only" in failed
    assert "readiness_recovers_after_fault" in failed
