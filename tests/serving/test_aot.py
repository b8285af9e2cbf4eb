"""AOT executable cache (serving/aot.py): fingerprint round trip,
cache-key invalidation (corrupt entry / bucket-list change / jax
version bump -> counted miss or error + silent recompile, never an
exception on the serving path), metrics families, /varz status, and
the serve-aot-build CLI."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving import aot
from keystone_tpu.serving.aot import AotStore
from keystone_tpu.serving.demo_model import build_pipeline

D = 16
EXAMPLE = jnp.zeros((D,), jnp.float32)


@pytest.fixture(scope="module")
def fitted():
    return build_pipeline(d=D, hidden=D, depth=2)


def make_store(tmp_path) -> AotStore:
    return AotStore(str(tmp_path / "aot"), registry=MetricsRegistry())


def warm_engine(fitted, store, buckets=(4, 8), name=None):
    eng = fitted.compiled(buckets=buckets, name=name, aot_store=store)
    eng.warmup(example=EXAMPLE)
    return eng


def statuses(engine):
    return {b: v["status"] for b, v in engine.aot_report().items()}


# -- the round trip --------------------------------------------------------

def test_roundtrip_second_engine_hits_with_zero_compiles(tmp_path, fitted):
    store = make_store(tmp_path)
    e1 = warm_engine(fitted, store, name="aot-rt-1")
    assert statuses(e1) == {4: "saved", 8: "saved"}
    assert e1.metrics.compile_count == 2  # the save generation compiles

    e2 = warm_engine(fitted, store, name="aot-rt-2")
    assert statuses(e2) == {4: "hit", 8: "hit"}
    # the whole point: NOT ONE trace or compile on the loaded engine
    assert e2.metrics.compile_count == 0
    assert store.hits == 2 and store.misses == 2 and store.errors == 0

    x = np.random.default_rng(0).standard_normal((5, D)).astype(np.float32)
    a = np.asarray(e1.apply(x, sync=True))
    b = np.asarray(e2.apply(x, sync=True))
    assert np.array_equal(a, b), "stored executable diverged from jit"


def test_hit_engine_still_registers_cost_models(tmp_path, fitted):
    """Device truth must survive the AOT path: the loaded executable's
    cost_analysis feeds the same MFU/goodput plane (this container's
    CPU backend reports cost analysis; the assert is conditional so a
    backend without it degrades to absent, not to a failure)."""
    store = make_store(tmp_path)
    e1 = warm_engine(fitted, store, name="aot-cm-1")
    e2 = warm_engine(fitted, store, name="aot-cm-2")
    if e1.metrics.cost_models:
        assert sorted(e2.metrics.cost_models) == sorted(
            e1.metrics.cost_models
        )


# -- cache-key invalidation ------------------------------------------------

def test_corrupt_entry_counts_error_and_recompiles(tmp_path, fitted):
    store = make_store(tmp_path)
    warm_engine(fitted, store, name="aot-c-1")
    for key in store.entries():
        with open(store.path_for(key), "wb") as f:
            f.write(b"not a pickle at all")
    e2 = warm_engine(fitted, store, name="aot-c-2")
    # every bucket fell back to a real compile, silently, and the
    # report says ERROR (matching the store counters) with the repair
    # (the broken entry was recompiled and re-saved) visible
    assert statuses(e2) == {4: "error", 8: "error"}
    assert {v.get("fallback") for v in e2.aot_report().values()} == {
        "saved"
    }
    assert e2.metrics.compile_count == 2
    assert store.errors == 2
    # and the fallback engine actually serves
    out = e2.apply(np.zeros((3, D), np.float32), sync=True)
    assert np.asarray(out).shape[0] == 3


def test_meta_mismatch_rejected_before_unpickling(tmp_path, fitted):
    """Defense in depth: an entry whose STORED meta (the plain-JSON
    preamble — readable without trusting the entry) disagrees with the
    requested fingerprint must not install, and the rejection happens
    before a single pickle byte is touched."""
    store = make_store(tmp_path)
    warm_engine(fitted, store, name="aot-t-1")
    key = store.entries()[0]
    stored = store.read_meta(key)
    assert stored is not None and stored["model_token"]
    loaded, outcome = store.load(
        key, dict(stored, model_token="someone-else")
    )
    assert loaded is None and outcome == "error"
    assert store.errors == 1
    # pickle never ran: the same entry still loads for the TRUE meta
    loaded, outcome = store.load(key, stored)
    assert loaded is not None and outcome == "hit"


def test_model_token_framing_blocks_adjacent_value_collisions():
    """Unframed hashing folded (1, 23) and (12, 3) to the same bytes;
    a token collision means one model serving another's predictions,
    so every hashed component is framed."""
    import hashlib

    def tok(v):
        h = hashlib.sha256()
        aot._hash_update(h, v)
        return h.hexdigest()

    assert tok([1, 23]) != tok([12, 3])
    assert tok([1, 23]) != tok(["1", 23])
    assert tok({"a": 1, "b": 2}) != tok({"a": 12, "b": ""})
    assert tok([[1], 2]) != tok([[1, 2]])


def test_changed_bucket_list_misses(tmp_path, fitted):
    """The bucket LIST is part of the fingerprint (not just the bucket):
    an engine re-bucketed to (4, 16) must not reuse the (4, 8) entry
    for bucket 4 — the stored program is correct either way, but a
    fingerprint that ignored the list would alias generations and make
    store bookkeeping unauditable."""
    store = make_store(tmp_path)
    warm_engine(fitted, store, buckets=(4, 8), name="aot-b-1")
    e2 = warm_engine(fitted, store, buckets=(4, 16), name="aot-b-2")
    assert statuses(e2) == {4: "saved", 16: "saved"}
    assert store.misses == 4 and store.errors == 0


def test_jax_version_bump_invalidates(tmp_path, fitted, monkeypatch):
    """A jax/jaxlib upgrade must produce a counted miss + silent
    recompile: serialized executables are PJRT bytes pinned to the
    toolchain that built them."""
    store = make_store(tmp_path)
    warm_engine(fitted, store, name="aot-v-1")
    monkeypatch.setattr(
        aot, "runtime_versions",
        lambda: {"jax": "99.0.0", "jaxlib": "99.0.0"},
    )
    e2 = warm_engine(fitted, store, name="aot-v-2")
    assert statuses(e2) == {4: "saved", 8: "saved"}
    assert e2.metrics.compile_count == 2
    assert store.hits == 0 and store.errors == 0


def test_unexecutable_entry_falls_back_and_charges_error(
    tmp_path, fitted, monkeypatch
):
    """An entry that deserializes but won't RUN (e.g. stale device
    topology) is uninstalled after the validation dispatch and the
    bucket recompiles — serving never sees the exception."""
    store = make_store(tmp_path)

    class Boom:
        def __call__(self, staged):
            raise RuntimeError("stale executable")

    monkeypatch.setattr(store, "load", lambda key, meta: (Boom(), "hit"))
    e = warm_engine(fitted, store, name="aot-x-1")
    assert statuses(e) == {4: "error", 8: "error"}
    assert store.errors == 2
    assert e.metrics.compile_count == 2
    out = e.apply(np.zeros((2, D), np.float32), sync=True)
    assert np.asarray(out).shape[0] == 2


def test_off_spec_input_detours_through_side_jit(tmp_path, fitted):
    """A stored executable is shape/dtype-rigid where jit is
    polymorphic: an off-spec input (here int32 rows) must serve like
    on a cold engine — never a TypeError out of apply() — WITHOUT
    costing on-spec traffic its zero-compile program."""
    store = make_store(tmp_path)
    warm_engine(fitted, store, name="aot-os-1")
    e2 = warm_engine(fitted, store, name="aot-os-2")
    assert statuses(e2)[4] == "hit"
    assert e2.metrics.compile_count == 0
    installed = e2._fns[4]
    x_int = np.arange(3 * D, dtype=np.int32).reshape(3, D)
    out = np.asarray(e2.apply(x_int, sync=True))
    e_jit = fitted.compiled(buckets=(4, 8), name="aot-os-jit",
                            aot_store=False)
    e_jit.warmup(example=EXAMPLE)
    assert np.array_equal(
        out, np.asarray(e_jit.apply(x_int, sync=True))
    )
    # the stray request traced ONE side program (exactly what a cold
    # engine would have done for that aval) and the stored executable
    # is still installed — on-spec traffic stays zero-compile
    assert e2.metrics.compile_count == 1
    assert e2._fns[4] is installed
    assert statuses(e2)[4] == "hit"
    x = np.zeros((2, D), np.float32)
    assert np.asarray(e2.apply(x, sync=True)).shape[0] == 2
    assert e2.metrics.compile_count == 1  # served by the stored exec
    # a second off-spec request reuses the cached side fn (jit's
    # per-aval cache) — no further compiles
    again = np.arange(2 * D, dtype=np.int32).reshape(2, D)
    assert np.asarray(e2.apply(again, sync=True)).shape[0] == 2
    assert e2.metrics.compile_count == 1


# -- fingerprint properties ------------------------------------------------

def test_pipeline_token_stable_across_use_and_distinguishes_weights():
    f1 = build_pipeline(d=8, hidden=8, depth=1)
    before = aot.pipeline_token(f1)
    # memoized on the pipeline (N lanes hash the model once, not N
    # times); drop the memo so the recompute below is a REAL one
    assert f1._aot_pipeline_token == before
    del f1._aot_pipeline_token
    eng = f1.compiled(buckets=(2,), aot_store=False)
    eng.warmup(example=jnp.zeros((8,), jnp.float32))
    # lazily-attached operator caches must not shift the token (a
    # token that changed when the pipeline RAN would turn every
    # restart into a miss)
    assert aot.pipeline_token(f1) == before
    f2 = build_pipeline(d=8, hidden=8, depth=2)
    assert aot.pipeline_token(f2) != before


def test_pipeline_token_hashes_graph_wiring():
    """Same operators in the same topo order, DIFFERENT edges: a
    multi-input node fed (A(x), x) vs (A(x), A(x)) computes different
    things, so the tokens must differ — likewise a re-pointed sink."""
    from keystone_tpu.workflow.api import FittedPipeline, Identity
    from keystone_tpu.workflow.graph import Graph

    g0 = Graph(
        sources=frozenset(), sink_dependencies={}, operators={},
        dependencies={},
    )
    g0, src = g0.add_source()
    g0, a = g0.add_node(Identity(), [src])

    # the token only hashes structure + operator identity, so Identity
    # stands in for a real multi-input join here
    g1, j1 = g0.add_node(Identity(), [a, src])
    g1, sink1 = g1.add_sink(j1)
    p1 = FittedPipeline(g1, src, sink1)

    g2, j2 = g0.add_node(Identity(), [a, a])
    g2, sink2 = g2.add_sink(j2)
    p2 = FittedPipeline(g2, src, sink2)

    assert aot.pipeline_token(p1) != aot.pipeline_token(p2)

    # sink re-pointed from the join back to the first node: same graph
    # body, different exposed value -> different token
    g3, sink3 = g2.add_sink(a)
    p3 = FittedPipeline(g3, src, sink3)
    assert aot.pipeline_token(p3) != aot.pipeline_token(p2)


def test_bucket_key_varies_by_every_field():
    specs = [((D,), np.float32)]
    base, _ = aot.bucket_key(specs, (4, 8), 4, donate=False,
                             shard=False, model_token="m")
    for kwargs in (
        dict(buckets=(4, 16)),
        dict(bucket=8),
        dict(donate=True),
        dict(shard=True),
        dict(model_token="other"),
    ):
        args = dict(specs=specs, buckets=(4, 8), bucket=4,
                    donate=False, shard=False, model_token="m")
        args.update(kwargs)
        key, _ = aot.bucket_key(**args)
        assert key != base, f"fingerprint ignored {kwargs}"
    other_spec, _ = aot.bucket_key(
        [((D,), np.float64)], (4, 8), 4, donate=False, shard=False,
        model_token="m",
    )
    assert other_spec != base


def test_bucket_key_featurize_token_isolates():
    """Fused device-featurize programs must never share an entry with
    the unfused model, nor with the same model fused behind a DIFFERENT
    featurizer — the featurize parameters are constants inside the
    serialized executable exactly like the model weights."""
    specs = [((8, 8, 3), np.uint8)]
    args = dict(specs=specs, buckets=(4,), bucket=4, donate=False,
                shard=False, model_token="m")
    plain, plain_meta = aot.bucket_key(**args)
    fused1, meta1 = aot.bucket_key(**args, featurize_token="f1")
    fused2, meta2 = aot.bucket_key(**args, featurize_token="f2")
    assert len({plain, fused1, fused2}) == 3
    # unfused meta carries NO featurize key: pre-featurize store
    # entries keep their fingerprints across the upgrade (no
    # fleet-wide cold start), while fused metas pin their token
    assert "featurize_token" not in plain_meta
    assert (meta1["featurize_token"], meta2["featurize_token"]) == (
        "f1", "f2"
    )


# -- device-featurize isolation --------------------------------------------

def _fused_pair():
    """Two featurize chains differing only in filter weights, plus a
    model sized to their shared output dim."""
    from keystone_tpu.serving.demo_model import build_pipeline
    from keystone_tpu.serving.featurize import build_featurize_pipeline

    feat1, feat_d = build_featurize_pipeline(
        img=8, channels=3, filters=4, conv_size=3,
        pool_stride=4, pool_size=4, seed=3,
    )
    feat2, _ = build_featurize_pipeline(
        img=8, channels=3, filters=4, conv_size=3,
        pool_stride=4, pool_size=4, seed=4,
    )
    model = build_pipeline(d=feat_d, hidden=8, depth=2)
    return feat1, feat2, model, feat_d


def _fused_engine(model, feat, store, name):
    eng = model.compiled(
        buckets=(4,), featurize=feat, aot_store=store, name=name
    )
    eng.warmup(example=jnp.zeros((8, 8, 3), jnp.uint8))
    return eng


def test_featurize_roundtrip_and_two_featurizers_never_collide(tmp_path):
    """The isolation contract end to end: a fused engine's entry hits
    for the SAME featurizer (zero compiles, identical outputs) and
    misses for a different one — which recompiles and serves its own
    correct answers, never the cached featurizer's."""
    feat1, feat2, model, feat_d = _fused_pair()
    store = make_store(tmp_path)
    raw = np.random.default_rng(5).integers(
        0, 256, (3, 8, 8, 3), dtype=np.uint8
    )

    e1 = _fused_engine(model, feat1, store, "aot-dfz-1")
    assert statuses(e1) == {4: "saved"}
    out1 = np.asarray(e1.apply(raw, sync=True))

    e2 = _fused_engine(model, feat1, store, "aot-dfz-2")
    assert statuses(e2) == {4: "hit"}
    assert e2.metrics.compile_count == 0
    np.testing.assert_array_equal(
        np.asarray(e2.apply(raw, sync=True)), out1
    )

    # different featurizer weights -> different fingerprint -> MISS
    # (never a hit on feat1's executable), fresh compile, own answers
    e3 = _fused_engine(model, feat2, store, "aot-dfz-3")
    assert statuses(e3) == {4: "saved"}
    assert e3.metrics.compile_count == 1
    out3 = np.asarray(e3.apply(raw, sync=True))
    want3 = np.asarray(
        model._batch_run(feat2._batch_run(jnp.asarray(raw)))
    )[:3]
    np.testing.assert_allclose(out3, want3, rtol=1e-4, atol=1e-6)
    assert not np.allclose(out3, out1)

    # and the unfused model shares nothing with the fused entries
    entries_before = set(store.entries())
    plain = model.compiled(buckets=(4,), aot_store=store, name="aot-dfz-p")
    plain.warmup(example=jnp.zeros((feat_d,), jnp.float32))
    assert statuses(plain) == {4: "saved"}
    assert set(store.entries()) > entries_before


def test_featurize_cross_load_falls_back_counted(tmp_path):
    """A cross-load attempt — feat1's entry bytes sitting at feat2's
    key (filename collision, copy mistake, hostile store) — is
    rejected on the meta re-check BEFORE anything is unpickled:
    counted as an error, recompiled, correct answer."""
    from keystone_tpu.serving.aot import pipeline_token, runtime_identity

    feat1, feat2, model, _feat_d = _fused_pair()
    store = make_store(tmp_path)
    e1 = _fused_engine(model, feat1, store, "aot-xl-1")
    assert statuses(e1) == {4: "saved"}

    specs = [((8, 8, 3), np.dtype(np.uint8))]
    ident = runtime_identity()
    key1, _ = aot.bucket_key(
        specs, e1.buckets, 4, donate=e1.donate, shard=False,
        model_token=pipeline_token(model), identity=ident,
        featurize_token=pipeline_token(feat1),
    )
    key2, _ = aot.bucket_key(
        specs, e1.buckets, 4, donate=e1.donate, shard=False,
        model_token=pipeline_token(model), identity=ident,
        featurize_token=pipeline_token(feat2),
    )
    # plant feat1's entry at feat2's key
    import shutil

    shutil.copyfile(store.path_for(key1), store.path_for(key2))
    errors_before = store.errors

    e2 = _fused_engine(model, feat2, store, "aot-xl-2")
    # the planted entry was rejected (stored meta disagrees with the
    # requested fingerprint), the error was counted, and the engine
    # recompiled its own program — never a wrong answer
    assert statuses(e2)[4] in ("error",)
    assert store.errors > errors_before
    assert e2.metrics.compile_count == 1
    raw = np.random.default_rng(6).integers(
        0, 256, (2, 8, 8, 3), dtype=np.uint8
    )
    want = np.asarray(
        model._batch_run(feat2._batch_run(jnp.asarray(raw)))
    )[:2]
    np.testing.assert_allclose(
        np.asarray(e2.apply(raw, sync=True)), want, rtol=1e-4, atol=1e-6
    )


def test_flagship_featurize_roundtrip_zero_compiles(tmp_path):
    """The flagship SIFT+LCS->FV chain — branched DAG, Pallas hot
    loops — through the AOT store: the save generation compiles, a
    second engine HITS with zero traces/compiles and serves bitwise-
    equal outputs (the serialized executable covers the whole fused
    program, Pallas lowering included), and ``pipeline_token``
    distinguishes the flagship chain from the demo conv chain so their
    entries can never collide."""
    from keystone_tpu.serving.aot import pipeline_token
    from keystone_tpu.serving.featurize import (
        build_featurize_pipeline,
        build_flagship_featurize_pipeline,
    )

    IMG = 34  # > the LCS keypoint border (2*16)
    flagship, feat_d = build_flagship_featurize_pipeline(
        img=IMG, desc_dim=8, vocab=8
    )
    model = build_pipeline(d=feat_d, hidden=8, depth=2)
    store = make_store(tmp_path)
    raw = np.random.default_rng(9).integers(
        0, 256, (3, IMG, IMG, 3), dtype=np.uint8
    )

    def engine(name):
        eng = model.compiled(
            buckets=(4,), featurize=flagship, aot_store=store, name=name
        )
        eng.warmup(example=jnp.zeros((IMG, IMG, 3), jnp.uint8))
        return eng

    e1 = engine("aot-fl-1")
    assert statuses(e1) == {4: "saved"}
    out1 = np.asarray(e1.apply(raw, sync=True))

    e2 = engine("aot-fl-2")
    assert statuses(e2) == {4: "hit"}
    assert e2.metrics.compile_count == 0
    np.testing.assert_array_equal(
        np.asarray(e2.apply(raw, sync=True)), out1
    )

    # the flagship fingerprint is its own: a demo conv chain with the
    # same uint8 input spec can never share an entry
    demo, _ = build_featurize_pipeline(img=IMG)
    assert pipeline_token(flagship) != pipeline_token(demo)
    assert pipeline_token(flagship) == pipeline_token(
        build_flagship_featurize_pipeline(img=IMG, desc_dim=8, vocab=8)[0]
    )


# -- observability ---------------------------------------------------------

def test_metrics_families_on_scrape(tmp_path, fitted):
    from keystone_tpu.observability.prometheus import render

    reg = MetricsRegistry()
    store = AotStore(str(tmp_path / "aot"), registry=reg)
    warm_engine(fitted, store, name="aot-m-1")  # misses + saves
    warm_engine(fitted, store, name="aot-m-2")  # hits
    text = render(reg.collect())
    assert "keystone_aot_cache_hits_total 2" in text
    assert "keystone_aot_cache_misses_total 2" in text
    # no errors happened: the family exists but carries no cells yet
    assert "# TYPE keystone_aot_cache_errors_total counter" in text
    assert "keystone_aot_cache_load_seconds_count 2" in text
    assert 'keystone_aot_cache_load_seconds_bucket{le="+Inf"} 2' in text


def test_configured_store_and_varz_status(tmp_path, monkeypatch, fitted):
    """setup_aot_cache -> configured_store -> the default "auto"
    engine path, and the aot_cache block on /varz's build info."""
    from keystone_tpu.observability import admin
    from keystone_tpu.parallel import runtime

    monkeypatch.setattr(runtime, "_aot_dir", None)
    monkeypatch.setattr(aot, "_configured", None)
    assert aot.configured_store() is None
    assert aot.status() == {"dir": None}

    root = str(tmp_path / "auto-aot")
    assert runtime.setup_aot_cache(root) == root
    store = aot.configured_store()
    assert store is not None and store.root == root
    # default engines (aot_store="auto") ride the configured store
    eng = fitted.compiled(buckets=(4,), name="aot-auto")
    eng.warmup(example=EXAMPLE)
    assert statuses(eng) == {4: "saved"}
    info = admin.build_info()
    assert info["aot_cache"]["dir"] == root
    assert info["aot_cache"]["entries"] == 1
    assert info["aot_cache"]["saves"] == 1


def test_setup_aot_cache_env_and_idempotence(tmp_path, monkeypatch):
    from keystone_tpu.parallel import runtime

    monkeypatch.setattr(runtime, "_aot_dir", None)
    monkeypatch.setenv("KEYSTONE_AOT_CACHE", str(tmp_path / "env-aot"))
    assert runtime.setup_aot_cache() == str(tmp_path / "env-aot")
    # idempotent: a second call (even with another arg) keeps the first
    assert runtime.setup_aot_cache(str(tmp_path / "other")) == str(
        tmp_path / "env-aot"
    )
    assert runtime.aot_cache_dir() == str(tmp_path / "env-aot")


# -- the serve-aot-build CLI -----------------------------------------------

def test_build_main_populates_then_hits(tmp_path, monkeypatch, capsys):
    from keystone_tpu.parallel import runtime

    monkeypatch.setattr(runtime, "_aot_dir", None)
    monkeypatch.setattr(aot, "_configured", None)
    # keep the process-global persistent compile cache out of the test
    monkeypatch.setattr(
        runtime, "setup_compilation_cache", lambda *a, **k: None
    )
    argv = ["--d", "8", "--hidden", "8", "--depth", "1",
            "--buckets", "2,4", "--aot-cache", str(tmp_path / "store")]
    assert aot.build_main(argv) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["aot"] == {"2": {"status": "saved"},
                             "4": {"status": "saved"}}
    assert report["entries"] == 2

    # second build: everything already stored -> hits, rc 0
    monkeypatch.setattr(runtime, "_aot_dir", None)
    monkeypatch.setattr(aot, "_configured", None)
    assert aot.build_main(argv) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {v["status"] for v in report["aot"].values()} == {"hit"}


# -- mesh-sharded program isolation (serving/sharding.py) ------------------

def test_bucket_key_sharding_token_isolates():
    """A mesh-sharded bucket program (GSPMD-partitioned, params as
    arguments) must never share an entry with the replicated program
    of the same model, nor with a different partitioning — while
    replicated fingerprints stay byte-identical to pre-sharding
    stores (no fleet-wide cold start on upgrade)."""
    specs = [((D,), np.float32)]
    args = dict(specs=specs, buckets=(4,), bucket=4, donate=False,
                shard=False, model_token="m")
    plain, plain_meta = aot.bucket_key(**args)
    shd1, meta1 = aot.bucket_key(**args, sharding_token="s1")
    shd2, meta2 = aot.bucket_key(**args, sharding_token="s2")
    assert len({plain, shd1, shd2}) == 3
    # replicated meta carries NO sharding key: existing entries keep
    # their fingerprints across the upgrade
    assert "sharding_token" not in plain_meta
    assert (meta1["sharding_token"], meta2["sharding_token"]) == (
        "s1", "s2"
    )
    # explicit None is the replicated fingerprint, byte for byte
    none_key, none_meta = aot.bucket_key(**args, sharding_token=None)
    assert none_key == plain and none_meta == plain_meta
    # and the two token kinds can't stand in for each other
    feat, _ = aot.bucket_key(**args, featurize_token="s1")
    assert feat != shd1


@pytest.fixture
def model_mesh():
    from keystone_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.make_mesh(n_data=1, n_model=8)
    with mesh_lib.use_mesh(m):
        yield m


def _sharded_engine(fitted, store, name, mesh):
    eng = fitted.compiled(
        buckets=(4,), name=name, aot_store=store,
        param_sharding=True, mesh=mesh,
    )
    eng.warmup(example=EXAMPLE)
    return eng


@pytest.mark.needs_mesh8
def test_sharded_roundtrip_and_replicated_never_collide(
    tmp_path, fitted, model_mesh
):
    """End to end: a sharded engine's entry hits for the SAME
    partitioning (zero compiles, identical outputs); the replicated
    engine for the same model gets its own distinct entry, never the
    sharded executable."""
    store = make_store(tmp_path)
    x = np.random.default_rng(2).standard_normal((3, D)).astype(
        np.float32
    )

    e1 = _sharded_engine(fitted, store, "aot-shd-1", model_mesh)
    assert statuses(e1) == {4: "saved"}
    out1 = np.asarray(e1.apply(x, sync=True))

    e2 = _sharded_engine(fitted, store, "aot-shd-2", model_mesh)
    assert statuses(e2) == {4: "hit"}
    assert e2.metrics.compile_count == 0
    np.testing.assert_array_equal(
        np.asarray(e2.apply(x, sync=True)), out1
    )

    # replicated engine, same model + specs: MISS, own entry
    entries_before = set(store.entries())
    plain = fitted.compiled(buckets=(4,), aot_store=store,
                            name="aot-shd-p")
    plain.warmup(example=EXAMPLE)
    assert statuses(plain) == {4: "saved"}
    assert set(store.entries()) > entries_before
    np.testing.assert_allclose(
        np.asarray(plain.apply(x, sync=True)), out1,
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.needs_mesh8
def test_sharded_cross_plant_rejected_at_meta_recheck(
    tmp_path, fitted, model_mesh
):
    """A sharded entry's bytes planted at the replicated key (and vice
    versa) are rejected on the stored-meta re-check BEFORE anything is
    unpickled: counted error, recompile, never a wrong program."""
    import shutil

    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.serving import sharding as sharding_lib
    from keystone_tpu.serving.aot import pipeline_token, runtime_identity

    store = make_store(tmp_path)
    e1 = _sharded_engine(fitted, store, "aot-xp-1", model_mesh)
    assert statuses(e1) == {4: "saved"}

    specs = [((D,), np.dtype(np.float32))]
    ident = runtime_identity()
    token = pipeline_token(fitted)
    shd_key, _ = aot.bucket_key(
        specs, (4,), 4, donate=e1.donate, shard=False,
        model_token=token, identity=ident,
        sharding_token=sharding_lib.sharding_token(
            e1.param_sharding, model_mesh
        ),
    )
    plain_key, _ = aot.bucket_key(
        specs, (4,), 4, donate=e1.donate, shard=False,
        model_token=token, identity=ident,
    )
    assert shd_key in store.entries()
    # plant the sharded entry at the replicated fingerprint
    shutil.copyfile(store.path_for(shd_key), store.path_for(plain_key))
    errors_before = store.errors

    plain = fitted.compiled(buckets=(4,), aot_store=store,
                            name="aot-xp-p")
    plain.warmup(example=EXAMPLE)
    assert statuses(plain)[4] == "error"
    assert store.errors > errors_before
    assert plain.metrics.compile_count == 1  # counted recompile
    x = np.random.default_rng(3).standard_normal((2, D)).astype(
        np.float32
    )
    np.testing.assert_allclose(
        np.asarray(plain.apply(x, sync=True)),
        np.asarray(e1.apply(x, sync=True)),
        rtol=1e-5, atol=1e-6,
    )

    # the reverse plant: replicated bytes at a DIFFERENT mesh's key
    m24 = mesh_lib.make_mesh(n_data=2, n_model=4)
    with mesh_lib.use_mesh(m24):
        other_key, _ = aot.bucket_key(
            specs, (4,), 4, donate=e1.donate, shard=False,
            model_token=token, identity=ident,
            sharding_token=sharding_lib.sharding_token(
                sharding_lib.resolve_param_sharding(True, fitted), m24
            ),
        )
        assert other_key not in store.entries()
        shutil.copyfile(
            store.path_for(shd_key), store.path_for(other_key)
        )
        e24 = _sharded_engine(fitted, store, "aot-xp-24", m24)
    assert statuses(e24)[4] == "error"
    assert e24.metrics.compile_count == 1
