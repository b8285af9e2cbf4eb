"""Model-id propagation through the fleet tier: replicas advertise
their zoo roster at ``/registerz``, the router forwards
``/predict/<model>`` path-preserved to ADVERTISING replicas only, and
a model nobody advertises is a typed 503 ``no_replica_for_model`` —
never a blind forward into a replica's 404. Plus the
``ReplicaRegistry`` model-filter unit behavior underneath."""

import itertools
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from keystone_tpu.fleet import RouterServer
from keystone_tpu.fleet.client import post_roster
from keystone_tpu.fleet.registry import ReplicaRegistry
from keystone_tpu.gateway import Gateway, GatewayServer
from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.zoo import (
    BuiltModel,
    ModelRegistry,
    ModelSpec,
    ModelZoo,
)

from gateway_fixtures import D, make_fitted

_ids = itertools.count()
ZD = 6  # the zoo replica's feature dim (matches gateway_fixtures.D)


def _post(url, doc, timeout=60):
    req = urllib.request.Request(
        url,
        data=json.dumps(doc).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _make_plain_replica(name):
    reg = MetricsRegistry()
    gw = Gateway(
        make_fitted(),
        buckets=(4, 8),
        n_lanes=1,
        max_delay_ms=1.0,
        warmup_example=np.zeros(D, np.float32),
        name=name,
        registry=reg,
    )
    srv = GatewayServer(gw, port=0, registry=reg).start()
    return gw, srv


def _make_zoo_replica(name, model_ids):
    reg = MetricsRegistry()
    registry = ModelRegistry()
    for i, mid in enumerate(model_ids):
        head = build_pipeline(d=ZD, hidden=8, depth=2, seed=i + 1)
        registry.register(ModelSpec(
            model_id=mid,
            build=lambda h=head: BuiltModel(fitted=h),
            buckets=(2, 4),
            lanes=1,
            max_delay_ms=1.0,
            warmup_example=np.zeros(ZD, np.float32),
            default=(i == 0),
        ))
    zoo = ModelZoo(
        registry, cse=False, aot_namespaces=False,
        metrics_registry=reg,
    )
    zoo.host()
    srv = GatewayServer(zoo=zoo, port=0, registry=reg).start()
    return zoo, srv


@pytest.fixture
def mixed_fleet():
    """One plain single-model replica (configured at startup, no
    roster) + one zoo replica self-registering with its model ids."""
    plain_gw, plain_srv = _make_plain_replica(
        f"models-plain{next(_ids)}"
    )
    zoo, zoo_srv = _make_zoo_replica(
        f"models-zoo{next(_ids)}", ("m1", "m2")
    )
    router = RouterServer(
        [plain_srv.url()],
        port=0,
        name=f"models-router{next(_ids)}",
        registry=MetricsRegistry(),
        probe_interval_s=0.1,
        probe_timeout_s=5.0,
        recovery_after_s=0.3,
    ).start()
    post_roster(
        router.url(), "/registerz", zoo_srv.url(),
        models=("m1", "m2"),
    )
    router.fleet.probe_once()
    yield router, (plain_gw, plain_srv), (zoo, zoo_srv)
    router.stop()
    plain_gw.close()
    plain_srv.stop()
    zoo.close()
    zoo_srv.stop()


def test_model_request_routes_to_advertising_replica(mixed_fleet):
    router, _, (zoo, _zoo_srv) = mixed_fleet
    doc = {"instances": [np.linspace(-1, 1, ZD).tolist()]}
    for _ in range(4):
        status, body = _post(router.url("/predict/m1"), doc)
        assert status == 200
        assert len(body["predictions"]) == 1
    # every forward landed on the advertiser: the zoo replica's m1
    # gateway served all of them
    assert (
        zoo.gateway_for("m1").metrics.outcome_count("ok") == 4.0
    )
    # the two heads answer differently through the same router
    _, m2 = _post(router.url("/predict/m2"), doc)
    _, m1 = _post(router.url("/predict/m1"), doc)
    assert m1["predictions"] != m2["predictions"]


def test_unadvertised_model_is_typed_503(mixed_fleet):
    router, _, _ = mixed_fleet
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(
            router.url("/predict/ghost"),
            {"instances": [[0.0] * ZD]},
        )
    assert ei.value.code == 503
    body = json.loads(ei.value.read())
    assert body["error"] == "no_replica_for_model"
    assert body["model"] == "ghost"
    # bare /predict still routes (any replica can serve it)
    status, _ = _post(
        router.url("/predict"), {"instances": [[0.0] * D]}
    )
    assert status == 200


def test_registerz_heartbeat_refreshes_models(mixed_fleet):
    router, _, (_zoo, zoo_srv) = mixed_fleet
    url = zoo_srv.url().rstrip("/")
    _, doc = _post(
        router.url("/registerz"),
        {"url": url, "models": ["m1", "m2", "m3"]},
    )
    assert not doc["created"]  # a heartbeat, not a new replica
    assert doc["models"] == ["m1", "m2", "m3"]
    row = next(
        r for r in router.fleet.roster()["replicas"]
        if r["url"] == url
    )
    assert row["models"] == ["m1", "m2", "m3"]
    # a heartbeat WITHOUT models leaves the roster untouched
    _, doc = _post(router.url("/registerz"), {"url": url})
    assert doc["models"] == ["m1", "m2", "m3"]


def test_registerz_rejects_bad_models_field(mixed_fleet):
    router, _, (_zoo, zoo_srv) = mixed_fleet
    for models in ("m1", [1, 2], {"m": 1}):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(
                router.url("/registerz"),
                {"url": zoo_srv.url(), "models": models},
            )
        assert ei.value.code == 400


# -- ReplicaRegistry model filter, no sockets -------------------------------


def _by_url(fleet, url):
    return next(r for r in fleet.replicas() if r.url == url)


def test_pick_filters_advertisers_before_health_tiers():
    fleet = ReplicaRegistry(["http://a:1", "http://b:2"])
    a = _by_url(fleet, "http://a:1")
    b = _by_url(fleet, "http://b:2")
    a.set_models(("m1",))
    # bare picks see both; model picks see only the advertiser —
    # even though b is equally healthy
    assert fleet.pick(model="m1") is a
    assert fleet.pick(model="m1", exclude=(a,)) is None
    # health fallbacks relax HEALTH, never the advertiser filter: an
    # unhealthy advertiser still beats a healthy non-advertiser
    for _ in range(3):
        a.mark_failed("boom")
    assert not a.healthy
    assert fleet.pick(model="m1") is a
    assert fleet.pick(model="m2") is None
    assert fleet.pick() in (a, b)


def test_registry_add_refreshes_models_and_status_reports_them():
    fleet = ReplicaRegistry()
    replica, created = fleet.add(
        "http://a:1", models=("zeta", "alpha")
    )
    assert created
    assert replica.advertises("zeta")
    assert not replica.advertises("omega")
    row = fleet.roster()["replicas"][0]
    assert row["models"] == ["alpha", "zeta"]
    # heartbeat with a new roster replaces; without one, keeps
    _, created = fleet.add("http://a:1", models=("m9",))
    assert not created
    assert replica.models == frozenset({"m9"})
    fleet.add("http://a:1")
    assert replica.models == frozenset({"m9"})
