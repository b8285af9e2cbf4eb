"""Device-side featurization (``CompiledPipeline(featurize=...)``):
the fused featurize∘model bucket programs must match the two-stage
host path numerically, keep the bounded-compile contract, account raw
H2D bytes exactly (`keystone_serving_h2d_bytes_total`), serve raw
uint8 through the batcher/pipeline bit-identically in serial and
pipelined modes, and survive gateway swaps with the fused stage
intact."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.batching import MicroBatcher
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.serving.featurize import build_featurize_pipeline

IMG, C = 8, 3
RAW_BYTES = IMG * IMG * C  # uint8: one byte per pixel-channel


@pytest.fixture(scope="module")
def featurize():
    # tiny geometry: 8x8x3 raw -> 3x3 conv (4 filters) -> rectify ->
    # 4/4 sum-pool -> vectorize; compile cost is milliseconds
    fitted, feat_d = build_featurize_pipeline(
        img=IMG, channels=C, filters=4, conv_size=3,
        pool_stride=4, pool_size=4, seed=3,
    )
    return fitted, feat_d


@pytest.fixture(scope="module")
def model(featurize):
    _, feat_d = featurize
    return build_pipeline(d=feat_d, hidden=8, depth=2)


def raw_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, IMG, IMG, C), dtype=np.uint8)


def fused_engine(model, featurize, buckets=(2, 4), name=None, **kw):
    feat, _ = featurize
    eng = model.compiled(
        buckets=buckets, featurize=feat, name=name, **kw
    )
    eng.warmup(example=jnp.zeros((IMG, IMG, C), jnp.uint8))
    return eng


def two_stage(model, featurize, raw):
    feat, _ = featurize
    feats = feat._batch_run(jnp.asarray(raw))
    return np.asarray(model._batch_run(feats))[: len(raw)]


def test_fused_matches_two_stage_with_bounded_compiles(model, featurize):
    eng = fused_engine(model, featurize, name="dfz-match")
    for n in (1, 2, 3, 4):
        raw = raw_batch(n, seed=n)
        got = np.asarray(eng.apply(raw, sync=True))
        want = two_stage(model, featurize, raw)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    # padded dispatches never contaminate valid rows, and the compile
    # count stays one per bucket however many sizes arrived
    assert eng.metrics.compiles.snapshot() == {2: 2, 4: 2} or (
        eng.metrics.compile_count == len(eng.buckets)
    )
    assert eng.metrics.compile_count == len(eng.buckets)


def test_oversized_raw_batch_chunks(model, featurize):
    eng = fused_engine(model, featurize, name="dfz-chunk")
    raw = raw_batch(9, seed=42)  # > max bucket 4: chunks 4+4+1
    got = np.asarray(eng.apply(raw, sync=True))
    np.testing.assert_allclose(
        got, two_stage(model, featurize, raw), rtol=1e-4, atol=1e-6
    )
    assert eng.metrics.compile_count == len(eng.buckets)


def test_h2d_bytes_accounts_raw_uint8(model, featurize):
    """The wire-bytes fact: a fused dispatch stages bucket * raw-uint8
    bytes; the same model behind host featurization stages bucket *
    feat_dim * 4 f32 bytes — the counter IS the reduction."""
    feat, feat_d = featurize
    eng = fused_engine(model, featurize, name="dfz-bytes")
    eng.apply(raw_batch(3), sync=True)  # bucket 4
    assert eng.metrics.h2d_bytes.snapshot() == {4: 4 * RAW_BYTES}
    s = eng.metrics.summary()
    assert s["h2d_bytes_total"] == 4 * RAW_BYTES
    assert s["h2d_bytes_per_example"] == round(4 * RAW_BYTES / 3, 1)

    plain = model.compiled(buckets=(2, 4), name="dfz-f32")
    plain.warmup(example=jnp.zeros((feat_d,), jnp.float32))
    feats = np.asarray(feat._batch_run(jnp.asarray(raw_batch(3))))[:3]
    plain.apply(feats, sync=True)
    assert plain.metrics.h2d_bytes.snapshot() == {4: 4 * feat_d * 4}


def test_h2d_bytes_family_on_scrape(model, featurize):
    reg = MetricsRegistry()
    eng = fused_engine(model, featurize, name="ignored")
    eng.metrics.register(registry=reg, engine="dfz-scrape")
    eng.apply(raw_batch(2), sync=True)
    fams = {f.name: f for f in reg.collect()}
    fam = fams["keystone_serving_h2d_bytes_total"]
    assert fam.mtype == "counter"
    samples = {
        s.labels["bucket"]: s.value
        for s in fam.samples
        if s.labels.get("engine") == "dfz-scrape"
    }
    assert samples == {"2": 2 * RAW_BYTES}


def test_batcher_raw_uint8_serial_vs_pipelined_bitwise(model, featurize):
    """Raw uint8 requests ride the batcher in ARRAY mode (no host
    hook): pooled uint8 staging buffers, fused dispatch, and the
    pipelined lane stays bit-identical to serial."""
    raws = [raw_batch(1, seed=100 + i)[0] for i in range(6)]
    rows = {}
    for depth in (0, 2):
        eng = fused_engine(model, featurize, name=f"dfz-mb-{depth}")
        with MicroBatcher(
            eng, max_delay_ms=100.0, pipeline_depth=depth
        ) as mb:
            futs = [mb.submit(r) for r in raws]
            rows[depth] = [np.asarray(f.result(timeout=60)) for f in futs]
        assert eng.metrics.examples.total == len(raws)
    for a, b in zip(rows[0], rows[2]):
        np.testing.assert_array_equal(a, b)


# -- the flagship chain ----------------------------------------------------

FIMG = 34  # must clear the LCS keypoint border (img > 2*16)


@pytest.fixture(scope="module")
def flagship():
    from keystone_tpu.serving.featurize import (
        build_flagship_featurize_pipeline,
    )

    # smallest honest geometry: every node class of the full chain
    # (gray->SIFT and LCS branches, PCA, GMM FV, Hellinger/L2, gather,
    # combine) at compile costs a CPU test run can afford
    return build_flagship_featurize_pipeline(
        img=FIMG, desc_dim=8, vocab=8
    )


def test_flagship_branched_dag_fuses_and_matches_two_stage(flagship):
    """The tentpole seam contract on the BRANCHED flagship DAG: the
    gather/combine graph composes through ``CompiledPipeline
    (featurize=)`` exactly like a linear chain — one program per
    bucket, raw uint8 staged and accounted exactly, fused outputs
    matching the two-stage host path at the repo's fusion tolerance
    (single-program XLA reassociates float ops across the seam)."""
    feat, feat_d = flagship
    model = build_pipeline(d=feat_d, hidden=8, depth=2)
    eng = model.compiled(
        buckets=(2, 4), featurize=feat, name="dfz-fl"
    )
    eng.warmup(example=jnp.zeros((FIMG, FIMG, C), jnp.uint8))
    assert eng.metrics.compile_count == len(eng.buckets)
    rng = np.random.default_rng(21)
    raw = rng.integers(0, 256, (3, FIMG, FIMG, C), dtype=np.uint8)
    got = np.asarray(eng.apply(raw, sync=True))
    feats = feat._batch_run(jnp.asarray(raw))
    want = np.asarray(model._batch_run(feats))[:3]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # no retrace on dispatch, and the wire carried raw pixels
    assert eng.metrics.compile_count == len(eng.buckets)
    assert eng.metrics.h2d_bytes.snapshot() == {4: 4 * FIMG * FIMG * C}


@pytest.fixture
def pinned_peaks(monkeypatch):
    """Known peaks for a device absent from the table (the CPU here)."""
    from keystone_tpu.observability import device as device_obs

    monkeypatch.setenv("KEYSTONE_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("KEYSTONE_PEAK_MEMBW_GBPS", "100")
    device_obs.reset_device_table()
    yield
    device_obs.reset_device_table()


def test_fused_flagship_program_publishes_its_device_series(
    flagship, pinned_peaks
):
    """The fused featurize-and-predict program has a cost model for every
    warmed bucket, and where the chip's peaks are known the series
    derived from it are present: a roofline class for each bucket and,
    after traffic, a utilization. Presence only — the values are a
    chip's to give."""
    feat, feat_d = flagship
    eng = build_pipeline(d=feat_d, hidden=8, depth=2).compiled(
        buckets=(2, 4), featurize=feat,
        name="dfz-fl-series",
    )
    eng.warmup(example=jnp.zeros((FIMG, FIMG, C), jnp.uint8))
    m = eng.metrics
    if not m.cost_models:
        pytest.skip("backend reports no XLA cost analysis")
    assert sorted(m.cost_models) == [2, 4]
    assert all(m.cost_models[b]["flops"] > 0 for b in (2, 4))
    assert {m.roofline_bound(b) for b in (2, 4)} <= {
        "compute", "bandwidth"
    }
    rng = np.random.default_rng(5)
    eng.apply(
        rng.integers(0, 256, (3, FIMG, FIMG, C), dtype=np.uint8),
        sync=True,
    )
    assert m.device_flops.total == m.cost_models[4]["flops"]
    assert m.mfu() is not None


def test_gateway_device_featurize_swap_keeps_fused_stage(model, featurize):
    """The full request plane over raw inputs: predicts match the
    two-stage reference, and a forced live rebucket rebuilds lane
    engines WITH the fused featurize stage (post-swap predicts still
    match and still stage raw bytes)."""
    from keystone_tpu.gateway import Gateway

    feat, _ = featurize
    raws = raw_batch(4, seed=7)
    want = two_stage(model, featurize, raws)
    with Gateway(
        model, buckets=(2, 4), n_lanes=1, max_delay_ms=2.0,
        device_featurize=feat,
        warmup_example=jnp.zeros((IMG, IMG, C), jnp.uint8),
        name="dfz-gw",
    ) as gw:
        got = [
            np.asarray(gw.predict(r).result(timeout=60)) for r in raws
        ]
        np.testing.assert_allclose(
            np.stack(got), want, rtol=1e-4, atol=1e-6
        )
        before = gw.pool.lanes[0].engine
        assert gw.rebucket(force=True)
        after = gw.pool.lanes[0].engine
        assert after is not before
        assert after.featurize is feat
        got2 = [
            np.asarray(gw.predict(r).result(timeout=60)) for r in raws
        ]
        np.testing.assert_allclose(
            np.stack(got2), want, rtol=1e-4, atol=1e-6
        )
        assert after.metrics.h2d_bytes.total > 0
