"""ServingMetrics: windowed rate, atomic latency snapshot, registry
bridge lifecycle."""

import gc

import pytest

from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.metrics import ServingMetrics
from keystone_tpu.utils.profiling import LatencyRecorder


def test_latency_recorder_p95_and_snapshot():
    rec = LatencyRecorder()
    for v in range(1, 101):  # 1..100 ms
        rec.record(v / 1000.0)
    assert rec.p95 is not None
    snap = rec.snapshot()
    assert snap["count"] == 100
    assert abs(snap["total"] - 5.05) < 1e-9
    assert abs(snap["p50"] - 0.0505) < 1e-3
    assert abs(snap["p95"] - 0.09505) < 1e-3
    assert abs(snap["p99"] - 0.09901) < 1e-3
    # empty recorder: percentiles None, zeros for count/total
    empty = LatencyRecorder().snapshot()
    assert empty == {
        "count": 0, "total": 0.0, "p50": None, "p95": None, "p99": None,
    }


class FakeClock:
    """Injectable ``ServingMetrics`` clock: elapsed time becomes a
    statement (``advance``), not a ``time.sleep`` that a loaded CI
    host can stretch — the windowed-rate tests below used to divide
    by real tiny lifetimes and flake under full-suite load."""

    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_windowed_rate_decays_to_zero_but_lifetime_does_not_jump():
    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    clk.advance(1.0)
    m.record_dispatch(bucket=8, n_valid=8, seconds=0.001)
    # fresh traffic: the windowed rate sees all 8 examples
    assert m.examples_per_sec() > 0
    # a very small window that has already passed: rate decays to zero
    clk.advance(0.05)
    assert m.examples_per_sec(window=0.01) == 0.0
    # the lifetime average still counts them (the documented wart the
    # windowed gauge exists to fix: lifetime dilutes over idle time,
    # windowed goes to zero)
    assert m.examples_per_sec_lifetime() > 0


def test_summary_uses_windowed_rate_and_snapshot_quantiles():
    m = ServingMetrics()
    for _ in range(4):
        m.record_dispatch(bucket=8, n_valid=8, seconds=0.002)
    s = m.summary()
    assert "examples_per_sec" in s
    assert "examples_per_sec_lifetime" in s
    assert s["examples_per_sec"] > 0
    assert s["dispatch_p95_ms"] is not None
    assert s["dispatch_p50_ms"] <= s["dispatch_p99_ms"]
    assert s["request_p95_ms"] is None  # no micro-batched requests yet


def test_request_size_histogram_accumulates():
    m = ServingMetrics()
    m.record_dispatch(bucket=8, n_valid=3, seconds=0.001)
    m.record_dispatch(bucket=8, n_valid=3, seconds=0.001)
    m.record_dispatch(bucket=64, n_valid=40, seconds=0.001)
    assert m.request_sizes.snapshot() == {3: 2, 40: 1}


def test_register_exports_and_prunes_after_gc():
    reg = MetricsRegistry()
    m = ServingMetrics()
    m.record_dispatch(bucket=8, n_valid=5, seconds=0.001)
    label = m.register(registry=reg, engine="e-test")
    assert label == "e-test"
    fams = {f.name for f in reg.collect()}
    assert "keystone_serving_compiles_total" in fams
    assert "keystone_serving_dispatch_latency_seconds" in fams
    del m
    gc.collect()
    assert not any("keystone_serving" in f.name for f in reg.collect())


def test_global_register_is_idempotent():
    m = ServingMetrics()
    first = m.register()
    assert m.register() == first  # no double export


def test_windowed_rate_clamps_oversized_window():
    """Events older than RATE_WINDOW_S are pruned at record time, so a
    window larger than that must clamp instead of silently dividing a
    30s sum by more seconds (4x undercount otherwise)."""
    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    m.record_dispatch(bucket=8, n_valid=8, seconds=0.001)
    clk.advance(0.05)
    lifetime = m.examples_per_sec()  # window = lifetime here (young)
    # the fake clock holds still between the reads, so the clamp is
    # EXACT (the real-clock version needed a 50 ms sleep and a wide
    # tolerance, and still flaked under host load)
    assert m.examples_per_sec(window=1e6) == pytest.approx(lifetime)
    assert m.examples_per_sec(window=1e6) > 0


def test_rate_events_prune_past_the_window():
    from keystone_tpu.serving.metrics import RATE_WINDOW_S

    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    m.record_dispatch(bucket=8, n_valid=8)
    # a full rate window plus slack later, a new dispatch prunes the
    # old event: only the fresh 2 examples remain countable
    clk.advance(RATE_WINDOW_S + 1.0)
    m.record_dispatch(bucket=8, n_valid=2)
    assert m.examples_per_sec() == pytest.approx(2 / RATE_WINDOW_S)
    assert len(m._rate_events) == 1


def test_same_label_reregistration_transfers_ownership():
    """The engine-swap loop re-registers a NEW metrics under the SAME
    label while the old engine is still alive: the newest owner wins
    and exactly one series set per label survives (duplicate series
    would fail a whole Prometheus scrape)."""
    reg = MetricsRegistry()
    old = ServingMetrics()
    old.record_dispatch(bucket=8, n_valid=1, seconds=0.001)
    new = ServingMetrics()
    for _ in range(3):
        new.record_dispatch(bucket=8, n_valid=2, seconds=0.001)
    old.register(registry=reg, engine="prod")
    new.register(registry=reg, engine="prod")
    samples = [
        s
        for f in reg.collect()
        if f.name == "keystone_serving_examples_total"
        for s in f.samples
        if s.labels.get("engine") == "prod"
    ]
    assert len(samples) == 1  # no duplicate series
    assert samples[0].value == 6  # the NEW engine's counter
    # the superseded collector pruned itself; old engine still alive
    assert old.examples.total == 1


def _render(reg):
    from keystone_tpu.observability.prometheus import render

    return render(reg.collect())


def test_goodput_families_golden_strings():
    """Per-bucket goodput accounting on the scrape surface: valid vs
    padded rows per bucket and the windowed padding-efficiency gauge."""
    reg = MetricsRegistry()
    m = ServingMetrics()
    m.register(registry=reg, engine="gp")
    m.record_dispatch(bucket=8, n_valid=5)
    m.record_dispatch(bucket=8, n_valid=8)
    m.record_dispatch(bucket=4, n_valid=1)
    text = _render(reg)
    for want in (
        '# TYPE keystone_serving_goodput_rows_total counter',
        'keystone_serving_goodput_rows_total{engine="gp",bucket="4"} 1',
        'keystone_serving_goodput_rows_total{engine="gp",bucket="8"} 13',
        'keystone_serving_padded_rows_total{engine="gp",bucket="4"} 3',
        'keystone_serving_padded_rows_total{engine="gp",bucket="8"} 3',
        '# TYPE keystone_serving_padding_efficiency gauge',
    ):
        assert want in text, f"missing {want!r} in:\n{text}"
    # 14 valid rows of 20 shipped
    assert m.padding_efficiency() == pytest.approx(14 / 20)
    assert (
        f'keystone_serving_padding_efficiency{{engine="gp"}} {14 / 20!r}'
        in text
    )


def test_device_cost_families_golden_strings():
    """Cost model + peaks -> flops-per-dispatch, temp-HBM, modeled
    FLOPs counter, rolling MFU, and the roofline one-hot."""
    reg = MetricsRegistry()
    m = ServingMetrics()
    m.register(registry=reg, engine="dev")
    m.set_cost_model(8, {
        "flops": 1000.0, "bytes_accessed": 10.0, "temp_bytes": 64.0,
    })
    m.set_cost_model(4, {
        "flops": 10.0, "bytes_accessed": 1000.0,
    })
    # ridge point = 1e6 / 1e4 = 100 flops/byte: bucket 8 (100 f/B) is
    # compute-bound, bucket 4 (0.01 f/B) bandwidth-bound
    m.set_device_peaks(1e6, 1e4, n_devices=1)
    m.record_dispatch(bucket=8, n_valid=6)
    text = _render(reg)
    for want in (
        'keystone_device_flops_per_dispatch{engine="dev",bucket="4"} 10',
        'keystone_device_flops_per_dispatch{engine="dev",bucket="8"} 1000',
        'keystone_device_bytes_per_dispatch{engine="dev",bucket="8"} 10',
        'keystone_device_temp_hbm_bytes{engine="dev",bucket="8"} 64',
        'keystone_serving_device_flops_total{engine="dev"} 1000',
        'keystone_device_roofline_bound{engine="dev",bucket="8",'
        'bound="compute"} 1',
        'keystone_device_roofline_bound{engine="dev",bucket="8",'
        'bound="bandwidth"} 0',
        'keystone_device_roofline_bound{engine="dev",bucket="4",'
        'bound="bandwidth"} 1',
        '# TYPE keystone_serving_mfu gauge',
        'keystone_serving_mfu{engine="dev"} ',
    ):
        assert want in text, f"missing {want!r} in:\n{text}"
    # bucket 4 has no temp_bytes: that cell is absent, not zero
    assert (
        'keystone_device_temp_hbm_bytes{engine="dev",bucket="4"}'
        not in text
    )
    assert m.mfu() is not None and m.mfu() > 0
    assert m.roofline_bound(8) == "compute"
    assert m.roofline_bound(4) == "bandwidth"


def test_device_families_absent_without_cost_model_or_peaks():
    """No cost analysis and unknown hardware -> NO device-truth series
    (absent, never zeros), while the classic families still export."""
    reg = MetricsRegistry()
    m = ServingMetrics()
    m.register(registry=reg, engine="bare")
    m.record_dispatch(bucket=8, n_valid=5)
    text = _render(reg)
    for absent in (
        "keystone_device_flops_per_dispatch",
        "keystone_device_bytes_per_dispatch",
        "keystone_device_temp_hbm_bytes",
        "keystone_device_roofline_bound",
        "keystone_serving_device_flops_total",
        "keystone_serving_mfu",
        "keystone_serving_staging_bytes",
    ):
        assert absent not in text, f"{absent} must be absent:\n{text}"
    assert 'keystone_serving_examples_total{engine="bare"} 5' in text
    # peaks without a cost model still yield no MFU (nothing to count)
    m.set_device_peaks(1e12, 1e11)
    assert m.mfu() is None
    # a cost model with peaks but no bytes_accessed: no roofline
    m.set_cost_model(8, {"flops": 5.0})
    assert m.roofline_bound(8) is None


def test_empty_cost_model_is_dropped():
    m = ServingMetrics()
    m.set_cost_model(8, {})
    assert m.cost_models == {}


def test_padding_efficiency_none_before_traffic_and_windowed():
    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    assert m.padding_efficiency() is None
    clk.advance(1.0)
    m.record_dispatch(bucket=8, n_valid=8)
    assert m.padding_efficiency() == pytest.approx(1.0)
    clk.advance(0.05)
    # outside the window: gauge decays to absent, not a stale 1.0
    assert m.padding_efficiency(window=0.01) is None


def test_mfu_scales_with_device_count():
    m = ServingMetrics()
    m.set_cost_model(8, {"flops": 100.0})
    m.record_dispatch(bucket=8, n_valid=8)
    # pin the windowed rate: MFU = flops/s over peak * n_devices
    m.flops_per_sec = lambda window=None: 500.0
    m.set_device_peaks(1e3, None, n_devices=1)
    assert m.mfu() == pytest.approx(0.5)
    m.set_device_peaks(1e3, None, n_devices=4)
    assert m.mfu() == pytest.approx(0.125)


def test_staging_bytes_gauge_exports_when_set():
    reg = MetricsRegistry()
    m = ServingMetrics()
    m.register(registry=reg, engine="stg")
    m.set_staging_bytes(4096)
    assert (
        'keystone_serving_staging_bytes{engine="stg"} 4096'
        in _render(reg)
    )


def test_engine_autoregisters_into_global_registry():
    from keystone_tpu.observability.registry import get_global_registry
    from keystone_tpu.serving.demo_model import build_pipeline

    fitted = build_pipeline(d=4, hidden=4, depth=1)
    engine = fitted.compiled(buckets=(2,), name="autoreg-test")
    assert engine.name == "autoreg-test"
    samples = [
        s
        for f in get_global_registry().collect()
        if f.name == "keystone_serving_examples_total"
        for s in f.samples
    ]
    assert any(s.labels.get("engine") == "autoreg-test" for s in samples)
