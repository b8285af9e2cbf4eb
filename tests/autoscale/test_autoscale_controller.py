"""Controller parsing unit tests: canned router surfaces ->
``FleetObservation``, and the windowed-p99 bucket arithmetic the
control loop reacts to (the lifetime quantile would never come back
down after one overload episode)."""

import math

import pytest

from keystone_tpu.autoscale.controller import (
    fleet_latency_buckets,
    observation_from,
    windowed_p99,
)

INF = float("inf")

METRICS = """\
# TYPE keystone_gateway_request_latency_seconds histogram
keystone_gateway_request_latency_seconds_bucket{gateway="r0",le="0.01"} 80
keystone_gateway_request_latency_seconds_bucket{gateway="r0",le="0.1"} 95
keystone_gateway_request_latency_seconds_bucket{gateway="r0",le="+Inf"} 100
keystone_gateway_request_latency_seconds_bucket{gateway="r1",le="0.01"} 40
keystone_gateway_request_latency_seconds_bucket{gateway="r1",le="0.1"} 50
keystone_gateway_request_latency_seconds_bucket{gateway="r1",le="+Inf"} 50
keystone_router_requests_total{router="r",status="ok"} 140
keystone_router_requests_total{router="r",status="shed"} 10
keystone_gateway_queue_depth{gateway="r0"} 3
keystone_gateway_inflight{gateway="r0"} 2
"""

FLEETZ = {
    "counts": {"healthy": 2, "half-open": 1},
    "replicas": [
        {"ready": True, "healthy": True},
        {"ready": True, "healthy": True},
        {"ready": False, "healthy": False},
    ],
}

SLZ = {
    "slos": [
        {"name": "other:latency", "burn_rate": {"fast": 9.0, "slow": 9.0}},
        {
            "name": "autoscaler:fleet_latency",
            "burn_rate": {"fast": 2.5, "slow": 0.8},
        },
    ]
}


def test_fleet_latency_buckets_merges_label_sets():
    buckets = fleet_latency_buckets(METRICS)
    assert buckets[0.01] == 120.0
    assert buckets[0.1] == 145.0
    assert buckets[INF] == 150.0


def test_observation_from_full_surfaces():
    obs = observation_from(METRICS, SLZ, FLEETZ, [], t=10.0)
    assert obs.replicas_total == 3
    assert obs.replicas_half_open == 1
    assert obs.replicas_ready == 2
    assert obs.burn_fast == 2.5 and obs.burn_slow == 0.8
    assert obs.load_total == 5.0
    assert obs.requests_total == 150.0
    # first tick: lifetime quantile (all 150 requests)
    assert obs.fleet_p99_s == pytest.approx(0.1, abs=0.05)


def test_observation_offered_rps_from_counter_delta():
    obs = observation_from(
        METRICS, None, FLEETZ, [], t=20.0,
        prev_requests=100.0, prev_t=10.0,
    )
    assert obs.offered_rps == pytest.approx(5.0)


def test_observation_degrades_on_absent_surfaces():
    obs = observation_from(None, None, None, [], t=0.0)
    assert obs.fleet_p99_s is None
    assert obs.burn_fast is None
    assert obs.replicas_total == 0
    assert obs.phase_shares == {}
    # a failed scrape is BLIND, not idle — the policy's cold path
    # keys off this flag
    assert obs.metrics_ok is False
    assert observation_from(METRICS, None, None, [], t=0.0).metrics_ok


def test_windowed_p99_reflects_only_the_window():
    base = {0.01: 1000.0, 0.1: 1000.0, INF: 1000.0}  # 1000 fast ones
    # the window adds 10 slow ones
    curr = {0.01: 1000.0, 0.1: 1000.0, INF: 1010.0}
    p99 = windowed_p99(curr, base)
    # ALL 10 window requests sit past the largest finite bound, which
    # the quantile clamps to — the SLOWEST representable value
    assert p99 == pytest.approx(0.1)
    # the lifetime view of the same snapshot reads fast (1000 of 1010
    # under 10ms) — exactly the signal a control loop must NOT use
    assert windowed_p99(curr, None) < 0.1


def test_windowed_p99_empty_window_is_none():
    snap = {0.01: 5.0, INF: 5.0}
    assert windowed_p99(snap, dict(snap)) is None
    assert windowed_p99({}, None) is None


def test_windowed_p99_clamps_membership_churn():
    """A deregistered replica removes its counts from the federation;
    the negative delta is membership churn, not traffic."""
    base = {0.01: 200.0, INF: 220.0}
    curr = {0.01: 120.0, INF: 130.0}  # counts went DOWN
    assert windowed_p99(curr, base) is None
    # one bucket shrank (churn, clamped to 0) while the tail grew:
    # the 10 genuinely-new slow requests still read as slow
    mixed = {0.01: 120.0, INF: 230.0}
    p99 = windowed_p99(mixed, base)
    assert p99 == pytest.approx(0.01)  # +Inf mass clamps to last finite
    assert not math.isinf(p99)


def test_phase_samples_land_in_observation():
    obs = observation_from(
        None, None, None,
        [{"queue_wait": 30.0, "device": 10.0}],
        t=0.0,
    )
    assert obs.dominant_phase == "queue_wait"
    assert obs.phase_shares["queue_wait"] == pytest.approx(0.75)


# -- the loop itself: reap -> observe -> decide -> act ----------------------


class ScriptedScraper:
    """``observe()`` from a script of (fleet p99 or None for a blind
    scrape); the clock advances 10 s a tick, past every cooldown."""

    def __init__(self, script):
        self.script = list(script)
        self.t = 0.0

    def observe(self):
        from keystone_tpu.autoscale.policy import FleetObservation

        self.t += 10.0
        p99 = self.script.pop(0)
        if p99 is None:
            return None
        return FleetObservation(t=self.t, fleet_p99_s=p99, metrics_ok=True)


def test_control_loop_scales_out_replaces_the_dead_and_retires_to_baseline():
    """The elasticity contract on scripted evidence, no clock and no
    sockets: ticks over the latency objective grow the fleet, a replica
    that dies is replaced outside the policy, a blind scrape decides
    nothing, and ticks far inside the objective drain-retire the fleet
    to its one-replica baseline — each decision counted and emitted."""
    from keystone_tpu.autoscale.controller import Autoscaler
    from keystone_tpu.autoscale.policy import PolicyConfig, PolicyEngine
    from keystone_tpu.observability.registry import MetricsRegistry

    from test_autoscale_supervisor import make

    launcher, sup = make()
    hot, cold = 0.5, 0.001
    scraper = ScriptedScraper([hot] * 4 + [None] + [cold] * 8)
    events = []
    loop = Autoscaler(
        sup, scraper,
        PolicyEngine(PolicyConfig(
            min_replicas=1, max_replicas=3, slo_latency_s=0.1,
            up_consecutive=2, down_consecutive=2,
            up_cooldown_s=5.0, down_cooldown_s=5.0,
        )),
        interval_s=1.0, registry=MetricsRegistry(), name="loop",
        on_event=events.append,
    )
    sup.scale_to(1)
    actions = [loop.tick().action for _ in range(4)]
    assert actions == ["hold", "scale_up", "hold", "scale_up"]
    assert sup.target == 3 and len(sup.replicas()) == 3
    # kill -9 one replica: the next tick repairs before it observes,
    # and that tick's scrape is blind, so nothing else is decided
    launcher.launched[0]._alive = False
    assert loop.tick() is None
    assert sup.replaced_total == 1 and len(sup.replicas()) == 3
    assert [e["event"] for e in events].count("replicas_replaced") == 1
    while scraper.script:
        loop.tick()
    assert sup.target == 1 and len(sup.replicas()) == 1
    decided = [e for e in events if e["event"] == "autoscale_decision"]
    assert len(decided) == 12  # every tick but the blind one
    assert max(e["running"] for e in decided) == 3
    assert [e["action"] for e in decided].count("scale_up") == 2
    assert [e["action"] for e in decided].count("scale_down") == 2
