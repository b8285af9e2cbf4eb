"""The zoo-mode HTTP frontend: per-model /predict/<model> routing,
the bare-/predict default model, the typed unknown-model 404 with the
registered ids, /planz, model-labeled zoo metrics on /metrics, and
the 404 copy enumerating the zoo routes — plus the single-model
server's typed refusal of model paths."""

import itertools
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from keystone_tpu.gateway import Gateway, GatewayServer
from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.zoo import (
    BuiltModel,
    ModelRegistry,
    ModelSpec,
    ModelZoo,
)

from gateway_fixtures import D as GW_D, make_fitted

D = 6
_ids = itertools.count()


def _spec(mid, seed, **kw):
    head = build_pipeline(d=D, hidden=8, depth=2, seed=seed)
    kw.setdefault("buckets", (2, 4))
    kw.setdefault("lanes", 1)
    kw.setdefault("max_delay_ms", 1.0)
    kw.setdefault("warmup_example", np.zeros(D, np.float32))
    return ModelSpec(
        model_id=mid, build=lambda: BuiltModel(fitted=head), **kw
    )


@pytest.fixture
def served_zoo():
    reg = MetricsRegistry()
    registry = ModelRegistry()
    registry.register(_spec("alpha", 1, default=True, pinned=True))
    registry.register(_spec("beta", 2))
    zoo = ModelZoo(
        registry, cse=False, aot_namespaces=False,
        metrics_registry=reg,
    )
    zoo.host()
    srv = GatewayServer(zoo=zoo, port=0, registry=reg).start()
    yield zoo, srv
    zoo.close()
    srv.stop()


def _get(srv, path):
    with urllib.request.urlopen(srv.url(path), timeout=15) as resp:
        return resp.status, resp.read().decode("utf-8")


def _post(srv, path, doc):
    req = urllib.request.Request(
        srv.url(path),
        data=json.dumps(doc).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def _post_error(srv, path, doc):
    try:
        _post(srv, path, doc)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    raise AssertionError(f"POST {path} unexpectedly succeeded")


def test_per_model_routing_and_default(served_zoo):
    _, srv = served_zoo
    doc = {"instances": [np.linspace(-1, 1, D).tolist()]}
    _, bare = _post(srv, "/predict", doc)
    _, alpha = _post(srv, "/predict/alpha", doc)
    _, beta = _post(srv, "/predict/beta", doc)
    # bare /predict serves the DEFAULT model, bit-for-bit
    assert bare["predictions"] == alpha["predictions"]
    assert alpha["predictions"] != beta["predictions"]


def test_unknown_model_typed_404(served_zoo):
    _, srv = served_zoo
    code, body = _post_error(
        srv, "/predict/nope", {"instances": [[0.0] * D]}
    )
    assert code == 404
    assert body["error"] == "unknown_model"
    assert body["model"] == "nope"
    assert sorted(body["registered"]) == ["alpha", "beta"]


def test_planz_reports_plan_vs_actual(served_zoo):
    zoo, srv = served_zoo
    status, raw = _get(srv, "/planz")
    assert status == 200
    doc = json.loads(raw)
    assert doc["default_model"] == "alpha"
    assert doc["plan"] is None  # no optimizer plan applied
    assert set(doc["actual"]) == {"alpha", "beta"}
    assert doc["actual"]["alpha"]["resident"] is True
    assert doc["actual"]["alpha"]["pinned"] is True
    assert doc["actual"]["alpha"]["lanes"] == 1


def test_metrics_carry_model_labels(served_zoo):
    _, srv = served_zoo
    _post(srv, "/predict/beta", {"instances": [[0.0] * D]})
    _, metrics = _get(srv, "/metrics")
    assert 'keystone_zoo_resident{model="alpha"} 1' in metrics
    assert 'keystone_zoo_resident{model="beta"} 1' in metrics
    assert 'keystone_zoo_pageins_total{model="beta"} 1' in metrics


def test_404_copy_enumerates_zoo_routes(served_zoo):
    _, srv = served_zoo
    try:
        _get(srv, "/nonexistent")
        raise AssertionError("GET /nonexistent unexpectedly 200")
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        assert e.code == 404
        assert "/predict/<model>" in body
        assert "/planz" in body
    try:
        _post(srv, "/nonexistent", {})
        raise AssertionError("POST /nonexistent unexpectedly 200")
    except urllib.error.HTTPError as e:
        assert e.code == 404
        assert "/predict/<model>" in e.read().decode()


def test_readyz_and_swap_in_zoo_mode(served_zoo):
    zoo, srv = served_zoo
    status, _ = _get(srv, "/readyz")
    assert status == 200
    status, swapped = _post(srv, "/swap", {})
    assert status == 200
    assert set(swapped["swapped"]) == {"alpha", "beta"}


def test_single_model_server_refuses_model_paths():
    reg = MetricsRegistry()
    gw = Gateway(
        make_fitted(),
        buckets=(2, 4),
        n_lanes=1,
        max_delay_ms=1.0,
        warmup_example=np.zeros(GW_D, np.float32),
        name=f"zoo-http-solo{next(_ids)}",
        registry=reg,
    )
    srv = GatewayServer(gw, port=0, registry=reg).start()
    try:
        code, body = _post_error(
            srv, "/predict/alpha", {"instances": [[0.0] * GW_D]}
        )
        assert code == 404
        assert body["error"] == "unknown_model"
        assert body["registered"] == []
        assert "--zoo" in body["detail"]
        # /planz is a zoo-mode route: typed 404 without one
        try:
            _get(srv, "/planz")
            raise AssertionError("/planz unexpectedly 200")
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert json.loads(e.read())["error"] == "no_zoo"
    finally:
        gw.close()
        srv.stop()


def test_server_requires_exactly_one_plane():
    with pytest.raises(ValueError, match="exactly one"):
        GatewayServer(port=0)
    registry = ModelRegistry()
    registry.register(_spec("solo", 1, default=True))
    zoo = ModelZoo(
        registry, cse=False, aot_namespaces=False,
        metrics_registry=MetricsRegistry(),
    )
    gw = Gateway(
        make_fitted(),
        buckets=(2,),
        n_lanes=1,
        warmup_example=np.zeros(GW_D, np.float32),
        name=f"zoo-http-both{next(_ids)}",
        registry=MetricsRegistry(),
    )
    try:
        with pytest.raises(ValueError, match="exactly one"):
            GatewayServer(gw, port=0, zoo=zoo)
    finally:
        gw.close()
        zoo.close()
