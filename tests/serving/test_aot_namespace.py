"""AOT store namespaces + GC (the model-zoo satellites): LRU-by-mtime
eviction within ONE namespace, pinned entries surviving any budget,
per-namespace byte gauges, and the isolation contracts — namespaced
fingerprints never collide across models, and a cross-namespace plant
is rejected off the stored meta before a pickle byte is touched."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.aot import AotStore, bucket_key
from keystone_tpu.serving.demo_model import build_pipeline

D = 16
EXAMPLE = jnp.zeros((D,), jnp.float32)


@pytest.fixture(scope="module")
def fitted():
    return build_pipeline(d=D, hidden=D, depth=2)


def _store(tmp_path, namespace=None):
    return AotStore(
        str(tmp_path / "aot"),
        registry=MetricsRegistry(),
        namespace=namespace,
    )


def _warm(fitted, store, buckets=(2, 4, 8), name="aot-ns"):
    eng = fitted.compiled(
        buckets=buckets, name=name, aot_store=store
    )
    eng.warmup(example=EXAMPLE)
    return eng


def _stamp_mtimes(store, keys):
    """Force a known LRU order: keys[0] oldest ... keys[-1] newest."""
    import os

    base = 1_700_000_000
    for i, key in enumerate(keys):
        os.utime(store.path_for(key), (base + i, base + i))


# -- gc ---------------------------------------------------------------------

def test_gc_evicts_lru_by_mtime(tmp_path, fitted):
    store = _store(tmp_path, namespace="m")
    _warm(fitted, store)
    keys = store.entries()
    assert len(keys) == 3
    _stamp_mtimes(store, keys)
    report = store.gc(0)
    # everything went, OLDEST FIRST — mtime is the LRU axis
    assert report["evicted"] == list(keys)
    assert report["kept_bytes"] == 0
    assert store.namespace_bytes() == 0


def test_gc_stops_at_the_budget(tmp_path, fitted):
    store = _store(tmp_path, namespace="m")
    _warm(fitted, store)
    keys = store.entries()
    _stamp_mtimes(store, keys)
    total = store.namespace_bytes()
    report = store.gc(total - 1)
    # one eviction (the least recently used) was enough
    assert report["evicted"] == [keys[0]]
    assert report["over_budget"] is False
    assert store.namespace_bytes() == report["kept_bytes"]


def test_gc_never_evicts_pinned(tmp_path, fitted):
    store = _store(tmp_path, namespace="m")
    _warm(fitted, store)
    keys = store.entries()
    _stamp_mtimes(store, keys)
    pinned = keys[0]  # the LRU victim-to-be
    report = store.gc(0, pinned=[pinned])
    assert pinned not in report["evicted"]
    assert sorted(report["evicted"]) == sorted(keys[1:])
    # the pin beat the byte target, and the report says so
    assert report["over_budget"] is True
    assert store.namespace_bytes() > 0


def test_gc_is_namespace_blind_to_other_models(tmp_path, fitted):
    other = build_pipeline(d=D, hidden=D, depth=2, seed=9)
    store_a = _store(tmp_path, namespace="model-a")
    store_b = AotStore(
        store_a.root, registry=MetricsRegistry(), namespace="model-b"
    )
    _warm(fitted, store_a, name="aot-ns-a")
    _warm(other, store_b, name="aot-ns-b")
    b_before = store_b.namespace_bytes()
    assert b_before > 0
    # model A's churn GCs model A — B's executables are invisible
    report = store_a.gc(0)
    assert store_a.namespace_bytes() == 0
    assert store_b.namespace_bytes() == b_before
    keys_b = store_b.entries()
    assert keys_b
    assert all(store_b.read_meta(k) is not None for k in keys_b)


def test_namespace_bytes_gauge_exported(tmp_path, fitted):
    store = _store(tmp_path, namespace="gauged")
    _warm(fitted, store)
    assert store.namespace_bytes() > 0
    assert store._bytes_g.get(("gauged",)) == float(
        store.namespace_bytes()
    )
    store.gc(0)
    assert store._bytes_g.get(("gauged",)) == 0.0


# -- fingerprint isolation --------------------------------------------------

def _key(**kw):
    kw.setdefault("specs", [((D,), "float32")])
    kw.setdefault("buckets", (2, 4))
    kw.setdefault("bucket", 2)
    kw.setdefault("donate", False)
    kw.setdefault("shard", False)
    kw.setdefault("model_token", "tok")
    kw.setdefault("identity", {"jax": "test"})
    return bucket_key(**kw)


def test_namespaces_never_collide_in_the_key():
    key_a, meta_a = _key(namespace="model-a")
    key_b, meta_b = _key(namespace="model-b")
    key_none, meta_none = _key()
    assert len({key_a, key_b, key_none}) == 3
    assert meta_a["namespace"] == "model-a"
    # single-model stores stay byte-identical to pre-zoo fingerprints:
    # no namespace field at all, so no fleet-wide cold start
    assert "namespace" not in meta_none


def test_featurize_and_sharding_tokens_never_collide():
    plain, _ = _key()
    feat_x, _ = _key(featurize_token="feat-x")
    feat_y, _ = _key(featurize_token="feat-y")
    shard_s, _ = _key(sharding_token="mesh-1x2")
    assert len({plain, feat_x, feat_y, shard_s}) == 4


def test_cross_namespace_plant_rejected(tmp_path, fitted):
    store_a = _store(tmp_path, namespace="model-a")
    _warm(fitted, store_a, name="aot-plant-a")
    key = store_a.entries()[0]
    meta_a = store_a.read_meta(key)
    assert meta_a["namespace"] == "model-a"
    # model B asks for the SAME filename with its own namespace (the
    # planted-entry attack): the stored preamble disagrees, so the
    # load is an ERROR and nothing was unpickled
    store_b = AotStore(
        store_a.root, registry=MetricsRegistry(), namespace="model-b"
    )
    loaded, outcome = store_b.load(
        key, dict(meta_a, namespace="model-b")
    )
    assert loaded is None and outcome == "error"
    assert store_b.errors == 1
    # the rightful owner still loads it
    loaded, outcome = store_a.load(key, meta_a)
    assert loaded is not None and outcome == "hit"
