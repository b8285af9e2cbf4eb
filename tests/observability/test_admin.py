"""Admin endpoint end-to-end (ephemeral port, fast) + stack-wide
integration: a live engine's counters in /metrics, executor node spans
in /tracez with parent links, Chrome trace export of a serving run.
"""

import json
import urllib.request

import numpy as np
import pytest

from keystone_tpu.observability import (
    AdminServer,
    MetricsRegistry,
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
)


def _get(server, path):
    with urllib.request.urlopen(server.url(path), timeout=10) as resp:
        return resp.status, resp.headers, resp.read().decode("utf-8")


@pytest.fixture
def traced():
    tracer = enable_tracing()
    tracer.clear()
    yield tracer
    disable_tracing()
    tracer.clear()


def test_healthz_and_404():
    with AdminServer(registry=MetricsRegistry(), tracer=Tracer()) as srv:
        status, _, body = _get(srv, "/healthz")
        assert status == 200 and body == "ok\n"
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv, "/nope")
        assert e.value.code == 404


def test_metrics_scrape_content_type_and_body():
    reg = MetricsRegistry()
    reg.counter("hits_total", "hits", ("path",)).inc(("/x",), by=3)
    with AdminServer(registry=reg, tracer=Tracer()) as srv:
        status, headers, body = _get(srv, "/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
    assert 'hits_total{path="/x"} 3' in body


def test_metrics_negotiates_openmetrics_for_exemplars():
    """A scraper sending the OpenMetrics Accept header (a real
    Prometheus server does by default) gets exemplar tails + # EOF;
    a plain scrape of the same registry stays classic v0.0.4 text
    with no mid-line '#' to trip the old parser."""
    reg = MetricsRegistry()
    reg.histogram("lat_s", "l", buckets=(1.0,)).observe(
        0.5, trace_id="tid42"
    )
    with AdminServer(registry=reg, tracer=Tracer()) as srv:
        req = urllib.request.Request(
            srv.url("/metrics"),
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            om_ctype = resp.headers["Content-Type"]
            om_body = resp.read().decode("utf-8")
        _, _, plain_body = _get(srv, "/metrics")
    assert om_ctype.startswith("application/openmetrics-text")
    assert '# {trace_id="tid42"}' in om_body
    assert om_body.endswith("# EOF\n")
    assert "# {" not in plain_body


def test_varz_json():
    reg = MetricsRegistry()
    reg.gauge("depth").set(2)
    with AdminServer(registry=reg, tracer=Tracer()) as srv:
        _, headers, body = _get(srv, "/varz")
    assert headers["Content-Type"].startswith("application/json")
    doc = json.loads(body)
    assert doc["depth"]["values"][0]["value"] == 2.0


def test_live_engine_scrape_end_to_end(traced):
    """Acceptance: GET /metrics on a live engine returns Prometheus text
    with per-bucket compile/dispatch counters and latency quantiles;
    /tracez shows the dispatch spans."""
    from keystone_tpu.serving.demo_model import build_pipeline

    reg = MetricsRegistry()
    fitted = build_pipeline(d=8, hidden=8, depth=2)
    engine = fitted.compiled(buckets=(4, 8))
    label = engine.metrics.register(registry=reg, engine="test-engine")
    assert label == "test-engine"
    rng = np.random.default_rng(0)
    engine.apply(rng.standard_normal((3, 8)).astype(np.float32), sync=True)
    engine.apply(rng.standard_normal((7, 8)).astype(np.float32), sync=True)

    with AdminServer(registry=reg, tracer=get_tracer()) as srv:
        _, _, metrics = _get(srv, "/metrics")
        _, _, tracez = _get(srv, "/tracez")
        _, _, healthz = _get(srv, "/healthz")

    assert healthz == "ok\n"
    want = [
        'keystone_serving_compiles_total{engine="test-engine",bucket="4"} 1',
        'keystone_serving_compiles_total{engine="test-engine",bucket="8"} 1',
        'keystone_serving_dispatches_total{engine="test-engine",bucket="4"} 1',
        'keystone_serving_dispatches_total{engine="test-engine",bucket="8"} 1',
        'keystone_serving_request_size_total{engine="test-engine",size="3"} 1',
        'keystone_serving_dispatch_latency_seconds{engine="test-engine",'
        'quantile="0.5"}',
        'keystone_serving_dispatch_latency_seconds{engine="test-engine",'
        'quantile="0.99"}',
        'keystone_serving_dispatch_latency_seconds_count'
        '{engine="test-engine"} 2',
        'keystone_serving_examples_total{engine="test-engine"} 10',
    ]
    for line in want:
        assert line in metrics, f"missing {line!r} in:\n{metrics}"

    spans = json.loads(tracez)["spans"]
    dispatches = [s for s in spans if s["name"] == "serving.dispatch"]
    assert len(dispatches) == 2
    assert {d["attrs"]["bucket"] for d in dispatches} == {4, 8}


def test_executor_node_spans_in_tracez_with_parent_links(traced, mesh8):
    """Acceptance: workflow executor node spans appear in /tracez, one
    per node around the node's own work, and the phases that ran inside
    a node (here the estimator's solver phases) link to it as parent."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import LinearRectifier
    from keystone_tpu.parallel.dataset import Dataset

    rng = np.random.default_rng(0)
    x = Dataset.from_array(rng.standard_normal((32, 8)).astype(np.float32))
    y = Dataset.from_array(rng.standard_normal((32, 2)).astype(np.float32))
    LinearRectifier(0.0).and_then(
        BlockLeastSquaresEstimator(4, num_iter=1, solve="host"), x, y
    ).fit()

    with AdminServer(registry=MetricsRegistry(), tracer=get_tracer()) as srv:
        _, _, body = _get(srv, "/tracez")
    doc = json.loads(body)
    assert doc["enabled"] is True
    nodes = [s for s in doc["spans"] if s["name"].startswith("node:")]
    assert len(nodes) >= 2
    # a node's dependencies are forced before its span opens: node spans
    # follow one another, none is another's child
    by_id = {s["span_id"]: s for s in nodes}
    assert not [s for s in nodes if s["parent_id"] in by_id]
    assert all(s["attrs"]["node_id"] for s in nodes)
    est = [s for s in nodes if "BlockLeastSquares" in s["name"]]
    assert len(est) == 1
    children = {
        s["name"] for s in doc["spans"]
        if s["parent_id"] == est[0]["span_id"]
    }
    assert {"solver.prep", "solver.block_stats", "solver.readback",
            "solver.host_solve", "solver.residual_update"} <= children


def test_chrome_trace_export_of_serving_run(traced, tmp_path):
    """Acceptance: a recorded serving run exports Chrome trace JSON
    that is structurally loadable (traceEvents of complete "X" events
    with numeric ts/dur) — the chrome://tracing / Perfetto format."""
    from keystone_tpu.serving import MicroBatcher
    from keystone_tpu.serving.demo_model import build_pipeline

    fitted = build_pipeline(d=8, hidden=8, depth=2)
    engine = fitted.compiled(buckets=(4,))
    engine.warmup(example=np.zeros((8,), np.float32))
    with MicroBatcher(engine, max_delay_ms=1.0) as mb:
        futs = [
            mb.submit(np.ones((8,), np.float32)) for _ in range(3)
        ]
        for f in futs:
            f.result(timeout=30)

    path = str(tmp_path / "serving_trace.json")
    get_tracer().export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "serving run recorded no spans"
    assert all(e["ph"] == "X" for e in events)
    assert all(
        isinstance(e["ts"], (int, float))
        and isinstance(e["dur"], (int, float))
        for e in events
    )
    names = {e["name"] for e in events}
    assert "serving.dispatch" in names
    assert "microbatch.coalesce" in names
    # the dispatch span parents under its coalesce window
    coalesce_ids = {
        e["args"]["span_id"]
        for e in events
        if e["name"] == "microbatch.coalesce"
    }
    dispatch_parents = {
        e["args"]["parent_id"]
        for e in events
        if e["name"] == "serving.dispatch"
    }
    assert dispatch_parents & coalesce_ids

    # /tracez?format=chrome serves the same document
    with AdminServer(registry=MetricsRegistry(), tracer=get_tracer()) as srv:
        _, _, body = _get(srv, "/tracez?format=chrome")
    assert {e["name"] for e in json.loads(body)["traceEvents"]} == names


def test_disabled_admin_means_no_server_and_no_spans():
    """The whole plane is off by default: the global tracer records
    nothing and engine construction alone opens no sockets (nothing to
    assert beyond: tracer off, span() is the null object)."""
    tracer = get_tracer()
    assert not tracer.enabled
    before = len(tracer.recent())
    with tracer.span("ghost"):
        pass
    assert len(tracer.recent()) == before


def test_varz_build_info_block():
    reg = MetricsRegistry()
    with AdminServer(registry=reg, tracer=Tracer()) as srv:
        _, _, body = _get(srv, "/varz")
        _, _, metrics = _get(srv, "/metrics")
    build = json.loads(body)["build"]
    for key in (
        "git_sha", "start_time_unix_s", "uptime_s", "pid",
        "python_version", "jax_version", "device_kind",
    ):
        assert key in build, f"missing {key} in build block: {build}"
    assert build["uptime_s"] >= 0
    # identity also on the scrape surface: constant info gauge +
    # standard process start time
    assert "# TYPE keystone_build_info gauge" in metrics
    assert 'keystone_build_info{git_sha="' in metrics
    assert "keystone_process_start_time_seconds" in metrics
    # the detected device table rides the build block (cached one-time
    # like the rest) and the scrape carries the device info gauge +
    # the memory sampler's family (host-RAM fallback on CPU backends)
    assert build["devices"], build
    assert build["devices"][0]["platform"] == "cpu"
    assert "peak_flops" in build["devices"][0]
    assert 'keystone_device_info{kind="' in metrics
    assert "keystone_device_memory_bytes{" in metrics


def test_slz_endpoint_renders_monitors():
    from keystone_tpu.observability.slo import Slo, SloMonitor

    reg = MetricsRegistry()
    mon = SloMonitor(
        fast_window_s=10, slow_window_s=100, registry=reg
    )
    state = {"total": 0.0, "bad": 0.0}
    mon.add(
        Slo(
            "adminz:api", 0.99,
            lambda: (state["total"], state["bad"]),
        )
    )
    mon.sample(now=0.0)
    state["total"], state["bad"] = 10.0, 1.0  # 10% bad in-window
    mon.sample(now=10.0)
    with AdminServer(registry=reg, tracer=Tracer()) as srv:
        _, headers, body = _get(srv, "/slz")
    assert headers["Content-Type"].startswith("application/json")
    doc = json.loads(body)
    (entry,) = [
        s for s in doc["slos"] if s["name"] == "adminz:api"
    ]
    assert entry["burn_rate"]["fast"] == pytest.approx(10.0)  # 10%/1%
    assert entry["breaching"] is True


def test_debugz_endpoint_lists_and_dumps_records(traced):
    from keystone_tpu.observability.flight import FlightRecorder

    reg = MetricsRegistry()
    rec = FlightRecorder(
        tracer=traced, latency_threshold_s=0.05, registry=reg
    )
    with traced.span("gateway.admit") as admit:
        with traced.span("serving.dispatch"):
            pass
    rec.maybe_capture(admit.trace_id, duration_s=0.2, gateway="gw-a")
    with AdminServer(registry=reg, tracer=traced) as srv:
        _, _, body = _get(srv, "/debugz")
        _, _, one = _get(srv, f"/debugz?trace_id={admit.trace_id}")
        _, _, chrome = _get(
            srv, f"/debugz?trace_id={admit.trace_id}&format=chrome"
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv, "/debugz?trace_id=deadbeef&format=chrome")
    assert e.value.code == 404
    doc = json.loads(body)
    assert doc["recorders"] >= 1
    assert any(r["trace_id"] == admit.trace_id for r in doc["records"])
    (record,) = json.loads(one)["records"]
    assert record["reason"] == "slo_breach"
    assert {s["name"] for s in record["spans"]} == {
        "gateway.admit", "serving.dispatch",
    }
    chrome_doc = json.loads(chrome)
    assert {e["name"] for e in chrome_doc["traceEvents"]} >= {
        "gateway.admit", "serving.dispatch",
    }
