"""Staged lane pipeline (serving/pipeline.py behind
``MicroBatcher(pipeline_depth=N)``): pipelined-vs-serial BIT identity
under mixed-size load, the single-entry fast path, mid-flight engine
swap (old-engine completion + staging-pool rebuild), host-featurize
items mode, buffer-pool reuse (no per-window host allocation growth),
backpressure shedding through the gateway, and the per-stage
metrics/bottleneck attribution."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.serving.batching import MicroBatcher
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.serving.pipeline import HostBufferPool

from test_engine import D, batch, make_fitted


@pytest.fixture(scope="module")
def fitted():
    return make_fitted()


def _run_bursts(mb, bursts):
    """Submit each burst, await it fully (deterministic windows: with a
    generous deadline every burst coalesces into exactly one window),
    return the rows in submission order."""
    rows = []
    for xs in bursts:
        futs = [mb.submit(x) for x in xs]
        rows.extend(np.asarray(f.result(timeout=60)) for f in futs)
    return rows


def test_pipelined_matches_serial_bitwise_mixed_sizes(fitted):
    """The tentpole's correctness bar: the staged pipeline composes the
    engine's same stage primitives over identical values, so outputs
    are BIT-identical to serial dispatch — across window sizes hitting
    every bucket, including the size-1 fast path."""
    rng = np.random.default_rng(31)
    sizes = [1, 3, 4, 7, 8, 2, 8, 1]
    bursts = [
        [rng.standard_normal(D).astype(np.float32) for _ in range(n)]
        for n in sizes
    ]
    serial_engine = CompiledPipeline(fitted, buckets=(4, 8))
    serial_engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        serial_engine, max_delay_ms=150.0, pipeline_depth=0
    ) as mb:
        want = _run_bursts(mb, bursts)

    piped_engine = CompiledPipeline(fitted, buckets=(4, 8))
    piped_engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        piped_engine, max_delay_ms=150.0, pipeline_depth=2
    ) as mb:
        got = _run_bursts(mb, bursts)

    assert len(got) == len(want) == sum(sizes)
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(
            g, w, err_msg=f"row {i} differs between serial and pipelined"
        )
    # the pipelined run actually went through the stage chain
    report = piped_engine.metrics.pipeline_report()
    assert report is not None and report["windows"] == len(sizes)


def test_pipelined_concurrent_load_matches_serial(fitted):
    """Concurrent mixed-size load: windows coalesce nondeterministically
    across 4 client threads, but every request's row still equals the
    serial batcher's row for the same input (row values are independent
    of window grouping through the bucketed program)."""
    engine = CompiledPipeline(fitted, buckets=(4, 16))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    n = 32
    xs = batch(n, seed=33)
    ref_engine = CompiledPipeline(fitted, buckets=(4, 16))
    ref_engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        ref_engine, max_delay_ms=100.0, pipeline_depth=0
    ) as mb:
        want = [
            np.asarray(f.result(timeout=60))
            for f in [mb.submit(x) for x in xs]
        ]
    futures = [None] * n
    with MicroBatcher(
        engine, max_delay_ms=5.0, pipeline_depth=2
    ) as mb:
        barrier = threading.Barrier(4)

        def client(tid):
            barrier.wait()
            for i in range(tid, n, 4):
                futures[i] = mb.submit(xs[i])
                if i % 3 == 0:
                    time.sleep(0.002)  # vary window composition

        threads = [
            threading.Thread(target=client, args=(t,)) for t in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rows = [np.asarray(f.result(timeout=60)) for f in futures]
    for i in range(n):
        np.testing.assert_array_equal(rows[i], want[i])
    assert engine.metrics.request_latency.count == n


def test_single_entry_fast_path_aliases_no_copy(fitted):
    """A one-request window skips the stack copy: ``_assemble`` lifts
    the caller's tree to a [1, ...] VIEW (owned=False), and the full
    path still returns the right row without corrupting the caller's
    buffer (the engine keeps its protective copy for unowned views)."""
    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    x = batch(1, seed=35)[0]
    keep = x.copy()
    with MicroBatcher(engine, max_delay_ms=5.0, pipeline_depth=2) as mb:
        lifted, owned = mb._assemble([x])
        assert owned is False
        assert lifted.shape == (1, D)
        assert np.shares_memory(lifted, x), "fast path must not copy"
        out = np.asarray(mb.submit(x).result(timeout=30))
    assert out.shape == (3,)
    np.testing.assert_array_equal(x, keep)  # caller's buffer untouched


def test_swap_engine_mid_flight_rebuilds_pool(fitted):
    """swap_engine under a pipelined lane: windows already in the
    stages finish on their coalesce-time engine, the host staging pool
    is rebuilt (generation bump — old-bucket buffers drop instead of
    re-pooling), and post-swap traffic runs on the replacement."""
    old = CompiledPipeline(fitted, buckets=(4,), name="pswap-old")
    old.warmup(example=jnp.zeros((D,), jnp.float32))
    new = CompiledPipeline(fitted, buckets=(2, 8), name="pswap-new")
    new.warmup(example=jnp.zeros((D,), jnp.float32))
    xs = batch(12, seed=37)
    ref = CompiledPipeline(fitted, buckets=(4,))
    ref.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(ref, max_delay_ms=100.0, pipeline_depth=0) as mb:
        want_old = [
            np.asarray(f.result(timeout=60))
            for f in [mb.submit(x) for x in xs[:4]]
        ]
    with MicroBatcher(old, max_delay_ms=5.0, pipeline_depth=2) as mb:
        pool = mb._pipeline.pool
        first = [mb.submit(x) for x in xs[:4]]
        for f, w in zip(first, want_old):
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=60)), w
            )
        gen0, alloc0 = pool.generation, pool.allocations
        assert alloc0 >= 1  # the first windows cut staging buffers
        returned = mb.swap_engine(new)
        assert returned is old
        assert pool.generation == gen0 + 1  # pool rebuilt on swap
        second = [mb.submit(x) for x in xs[4:]]
        rows = [np.asarray(f.result(timeout=60)) for f in second]
    assert all(r.shape == (3,) for r in rows)
    # post-swap traffic ran on the replacement engine, and its windows
    # cut NEW staging buffers (the old engine's are dropped, not reused)
    assert new.metrics.examples.total == 8
    assert old.metrics.examples.total == 4
    assert pool.allocations > alloc0


def test_buffer_pool_reuse_no_allocation_growth(fitted):
    """Steady-state same-bucket windows reuse pooled staging buffers:
    after the pool primes, more windows add ZERO host allocations."""
    engine = CompiledPipeline(fitted, buckets=(8,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    n_windows = 12
    with MicroBatcher(
        engine, max_delay_ms=100.0, max_batch=8, pipeline_depth=2
    ) as mb:
        pool = mb._pipeline.pool
        for k in range(n_windows):
            xs = batch(8, seed=100 + k)
            for f in [mb.submit(x) for x in xs]:
                f.result(timeout=60)
        allocations = pool.allocations
    assert engine.metrics.windows.total == n_windows
    # sequential awaited windows recycle one buffer; the bound below is
    # the pool's absolute cap (depth+1 per key), not per-window growth
    assert allocations <= pool.max_per_key, (
        f"{allocations} host staging allocations for {n_windows} windows"
    )


def test_host_featurize_items_mode(fitted):
    """The pluggable host-featurize hook (items-mode/tokenizer
    front-ends behind the engine): clients submit RAW items (here:
    python lists), the prep stage turns each coalesced window into the
    batched array tree — identically in serial and pipelined modes."""
    weights = np.linspace(0.5, 1.5, D).astype(np.float32)

    def featurize(items):
        # a stand-in for a fused tokenizer: list[list[float]] -> [n, D]
        return np.stack(
            [np.asarray(it, np.float32) * weights for it in items]
        )

    rng = np.random.default_rng(41)
    items = [list(rng.standard_normal(D).astype(np.float32)) for _ in range(6)]

    rows = {}
    for depth in (0, 2):
        engine = CompiledPipeline(fitted, buckets=(8,))
        engine.warmup(example=jnp.zeros((D,), jnp.float32))
        with MicroBatcher(
            engine, max_delay_ms=100.0, pipeline_depth=depth,
            host_featurize=featurize,
        ) as mb:
            futs = [mb.submit(it) for it in items]
            rows[depth] = [
                np.asarray(f.result(timeout=60)) for f in futs
            ]
        # raw items coalesced into shared windows (one spec stream)
        assert engine.metrics.max_coalesced >= 2
    for a, b in zip(rows[0], rows[2]):
        np.testing.assert_array_equal(a, b)


def test_backpressure_sheds_typed_overloaded(fitted):
    """End-to-end backpressure: a slow host-featurize stage fills the
    bounded stage queues, submit_window blocks the dispatcher, pending
    piles up behind the lanes, and the gateway's admission controller
    sheds the flood with typed Overloaded errors while every admitted
    request still resolves."""
    from keystone_tpu.gateway import Gateway, Overloaded
    from keystone_tpu.observability.registry import MetricsRegistry

    def slow_featurize(items):
        time.sleep(0.02)  # make host-prep the narrow stage
        return np.stack([np.asarray(it, np.float32) for it in items])

    xs = batch(8, seed=43)
    with Gateway(
        fitted, buckets=(4,), n_lanes=1, max_delay_ms=1.0,
        warmup_example=np.zeros(D, np.float32),
        registry=MetricsRegistry(), name="bp-gw",
        pipeline_depth=1, host_featurize=slow_featurize,
        max_pending=8, lane_capacity=4,
    ) as gw:
        admitted, shed = [], []
        deadline = time.perf_counter() + 20
        while not shed and time.perf_counter() < deadline:
            try:
                admitted.append(gw.predict(xs[len(admitted) % 8]))
            except Overloaded as e:
                shed.append(e)
        assert shed, "flood never hit the backpressure bound"
        assert shed[0].reason == "queue_full"
        for f in admitted:
            assert np.asarray(f.result(timeout=60)).shape == (3,)
        assert gw.metrics.shed_count("queue_full") >= 1


def test_stage_metrics_and_bottleneck_attribution(fitted):
    """After pipelined traffic every stage has a seconds series, the
    lane attributes a bottleneck stage, overlap efficiency is defined,
    and the stage families export through the registry scrape."""
    from keystone_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.metrics.register(reg, engine="stage-metrics")
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        engine, max_delay_ms=50.0, max_batch=4, pipeline_depth=2
    ) as mb:
        for k in range(4):
            for f in [mb.submit(x) for x in batch(4, seed=50 + k)]:
                f.result(timeout=60)
        report = engine.metrics.pipeline_report()
    assert report["windows"] == 4
    assert set(report["stages"]) == {
        "host_prep", "upload", "compute", "deliver"
    }
    assert report["bottleneck"] in report["stages"]
    assert report["overlap_efficiency"] is not None
    for stage in report["stages"].values():
        assert stage["mean_ms"] >= 0
        assert stage["rate_per_s"] > 0
    from keystone_tpu.observability.prometheus import render

    text = render(reg.collect())
    assert "keystone_serving_stage_seconds" in text
    assert 'stage="host_prep"' in text
    assert "keystone_serving_pipeline_windows_total" in text
    assert "keystone_serving_pipeline_bottleneck" in text
    assert "keystone_serving_pipeline_overlap_efficiency" in text


def test_serial_engine_scrape_has_no_stage_series(fitted):
    """Serial engines never emit empty pipeline families."""
    from keystone_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.metrics.register(reg, engine="serial-only")
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    engine.apply(batch(3, seed=55), sync=True)
    from keystone_tpu.observability.prometheus import render

    text = render(reg.collect())
    assert "keystone_serving_stage_seconds" not in text
    assert "keystone_serving_dispatches_total" in text


def test_dispatch_latency_completion_vs_enqueue(fitted):
    """The dispatch-accounting fix: ``serving.dispatch`` latency is now
    completion-timed (recorded at the sync point), while the old
    enqueue-only number survives as its own series."""
    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    engine.apply(batch(3, seed=57), sync=True)
    m = engine.metrics
    # warmup syncs per bucket + the apply: both series populated,
    # completion-timed and enqueue-timed counted independently
    assert m.dispatch_latency.count >= 1
    assert m.dispatch_enqueue_latency.count >= 1
    # pipelined compute stage records the completion series too
    piped = CompiledPipeline(fitted, buckets=(4,))
    piped.warmup(example=jnp.zeros((D,), jnp.float32))
    base = piped.metrics.dispatch_latency.count
    with MicroBatcher(piped, max_delay_ms=5.0, pipeline_depth=2) as mb:
        mb.submit(batch(1, seed=58)[0]).result(timeout=30)
    assert piped.metrics.dispatch_latency.count == base + 1


def test_oversized_pinned_window_falls_back_serial(fitted):
    """A pinned max_batch wider than a post-swap engine's largest
    bucket degrades to the engine's chunked serial apply inside the
    compute stage — degraded, never wrong."""
    old = CompiledPipeline(fitted, buckets=(8,))
    old.warmup(example=jnp.zeros((D,), jnp.float32))
    small = CompiledPipeline(fitted, buckets=(4,))
    small.warmup(example=jnp.zeros((D,), jnp.float32))
    xs = batch(8, seed=61)
    ref = CompiledPipeline(fitted, buckets=(4,))
    ref.warmup(example=jnp.zeros((D,), jnp.float32))
    want = np.asarray(ref.apply(xs, sync=True))
    with MicroBatcher(
        old, max_delay_ms=10_000.0, max_batch=8, pipeline_depth=2
    ) as mb:
        mb.swap_engine(small)  # largest bucket (4) < pinned max_batch (8)
        futs = [mb.submit(x) for x in xs]  # fills one window of 8
        rows = np.stack(
            [np.asarray(f.result(timeout=60)) for f in futs]
        )
    np.testing.assert_array_equal(rows, want)


def test_stage_error_resolves_futures_and_recycles(fitted):
    """A failure inside a stage resolves that window's futures with the
    error (never hangs callers) and the NEXT window still works — the
    stage threads survive and pooled buffers aren't leaked."""
    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(engine, max_delay_ms=5.0, pipeline_depth=2) as mb:
        bad = mb.submit(np.zeros(D + 1, np.float32))  # wrong width:
        # fails at trace/compute time inside the stage chain
        with pytest.raises(Exception):
            bad.result(timeout=60)
        good = mb.submit(batch(1, seed=63)[0])
        assert np.asarray(good.result(timeout=60)).shape == (3,)


def test_host_prep_failure_does_not_poison_pool(fitted):
    """A featurize hook returning leaves with mismatched leading dims
    makes host_stage fail AFTER the window's staging buffers were
    acquired. The futures must get the error, the REAL buffers must go
    back to the pool (releasing the half-built window's host_tree=None
    used to poison that (bucket, spec) key: every later window sharing
    it popped the None instead of allocating), and the lane must keep
    serving."""
    def featurize(items):
        if any(i == "poison" for i in items):
            # two leaves, second with a leading dim that can't
            # broadcast into the (rows, D) staging buffer
            return (
                np.zeros((len(items), D), np.float32),
                np.zeros((len(items) + 1, D), np.float32),
            )
        return np.stack(
            [np.full((D,), float(len(s)), np.float32) for s in items]
        )

    engine = CompiledPipeline(fitted, buckets=(4,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        engine, max_delay_ms=5.0, pipeline_depth=2, host_featurize=featurize
    ) as mb:
        for _ in range(2):  # same poisoned key twice: the second window
            # must re-acquire a usable buffer, not a pooled None
            bad = mb.submit("poison")
            with pytest.raises(Exception):
                bad.result(timeout=60)
        pool = mb._pipeline.pool
        assert all(
            b is not None
            for bufs in pool._free.values()
            for b in bufs
        )
        good = mb.submit("abc")
        assert np.asarray(good.result(timeout=60)).shape == (3,)


def test_goodput_counters_bitwise_against_window_shapes(fitted):
    """Device-truth goodput accounting through the lane pipeline's
    compute stage: known window shapes -> EXACT per-bucket valid/padded
    row counts (the same ``record_dispatch`` path the serial engine
    uses — one code path, same numbers)."""
    engine = CompiledPipeline(fitted, buckets=(4, 8))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    # bursts of 3, 4, 7: windows of exactly those sizes (generous
    # coalesce deadline), dispatching buckets 4, 4, 8
    with MicroBatcher(
        engine, max_delay_ms=150.0, pipeline_depth=2
    ) as mb:
        _run_bursts(
            mb,
            [
                [batch(1, seed=70 + i)[0] for _ in range(n)]
                for i, n in enumerate((3, 4, 7))
            ],
        )
    m = engine.metrics
    assert m.examples.snapshot() == {4: 7, 8: 7}
    assert m.padded_rows.snapshot() == {4: 1, 8: 1}
    assert m.examples.total == 14
    assert m.padded_rows.total == 2
    # efficiency gauge agrees bitwise with the counters: 14 / 16
    assert m.padding_efficiency() == pytest.approx(14 / 16)


def test_staging_bytes_gauge_tracks_pool(fitted):
    """The HostBufferPool's live byte accounting reaches the engine's
    staging-bytes gauge, and pooled + outstanding bytes return to the
    pooled side once windows complete."""
    engine = CompiledPipeline(fitted, buckets=(8,))
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    with MicroBatcher(
        engine, max_delay_ms=50.0, max_batch=8, pipeline_depth=2
    ) as mb:
        pool = mb._pipeline.pool
        for f in [mb.submit(x) for x in batch(8, seed=80)]:
            f.result(timeout=60)
        # one 8-row float32 staging buffer of width D
        expect = 8 * D * 4
        assert pool.staging_bytes == expect * (
            pool.allocations
        )
        assert engine.metrics.staging_bytes == pool.staging_bytes
        # a swap resets the accounting with the pool AND the gauge —
        # a /metrics scrape right after the swap must not export the
        # pre-swap footprint
        mb.swap_engine(engine)
        assert pool.staging_bytes == 0
        assert engine.metrics.staging_bytes == 0
        # swap to an engine with its OWN metrics: post-swap windows
        # gauge the current engine's series only — the retired one
        # stays zeroed (no cross-engine double count)
        engine2 = CompiledPipeline(fitted, buckets=(8,), name="swap-tgt")
        engine2.warmup(example=jnp.zeros((D,), jnp.float32))
        mb.swap_engine(engine2)
        for f in [mb.submit(x) for x in batch(8, seed=81)]:
            f.result(timeout=60)
        assert engine.metrics.staging_bytes == 0
        assert engine2.metrics.staging_bytes == pool.staging_bytes > 0


class TestHostBufferPool:
    def test_acquire_reuse_and_cap(self):
        pool = HostBufferPool(max_per_key=2)
        gen, a = pool.acquire("k", lambda: np.zeros(4))
        assert pool.allocations == 1
        pool.release("k", gen, a)
        gen2, b = pool.acquire("k", lambda: np.zeros(4))
        assert b is a and pool.allocations == 1  # reused, no realloc
        # cap: releasing more than max_per_key drops the excess
        extras = [pool.acquire("k", lambda: np.zeros(4))[1] for _ in range(3)]
        for buf in [b] + extras:
            pool.release("k", gen2, buf)
        assert len(pool._free["k"]) == 2

    def test_generation_bump_drops_stale_buffers(self):
        pool = HostBufferPool()
        gen, a = pool.acquire("k", lambda: np.zeros(4))
        pool.reset()  # engine swap
        pool.release("k", gen, a)  # stale generation: dropped
        assert not pool._free.get("k")
        gen2, b = pool.acquire("k", lambda: np.zeros(4))
        assert gen2 == gen + 1 and b is not a

    def test_release_none_is_dropped(self):
        pool = HostBufferPool()
        gen, _ = pool.acquire("k", lambda: np.zeros(4))
        pool.release("k", gen, None)  # window died pre-attachment
        assert not pool._free.get("k")


def test_uint8_staging_pool_reuse_and_byte_accounting():
    """Device-featurize lanes stage RAW uint8: the per-(bucket, spec)
    pool keys carry the uint8 dtype, steady-state windows reuse the
    pooled raw buffers (zero allocation growth past the cap), and
    both the pool's byte ledger and the staging-bytes gauge account
    the one-byte-per-element footprint exactly (the f32 ledger would
    be 4x this for the same element count)."""
    from keystone_tpu.serving.demo_model import build_pipeline
    from keystone_tpu.serving.featurize import build_featurize_pipeline

    img, ch = 8, 3
    feat, feat_d = build_featurize_pipeline(
        img=img, channels=ch, filters=4, conv_size=3,
        pool_stride=4, pool_size=4, seed=3,
    )
    model = build_pipeline(d=feat_d, hidden=8, depth=2)
    engine = model.compiled(
        buckets=(4,), featurize=feat, aot_store=False, name="u8-pool"
    )
    engine.warmup(example=jnp.zeros((img, img, ch), jnp.uint8))
    rng = np.random.default_rng(9)
    n_windows = 10
    with MicroBatcher(
        engine, max_delay_ms=100.0, max_batch=4, pipeline_depth=2
    ) as mb:
        pool = mb._pipeline.pool
        for k in range(n_windows):
            raws = rng.integers(0, 256, (4, img, img, ch), dtype=np.uint8)
            for f in [mb.submit(r) for r in raws]:
                f.result(timeout=60)
        allocations = pool.allocations
        # the pool key pins the raw uint8 spec, and its cached size is
        # the raw byte footprint: bucket rows x img x img x ch x 1 B
        raw_buf_bytes = 4 * img * img * ch
        keys = list(pool._key_bytes)
        assert len(keys) == 1
        (bucket, _treedef, leaf_specs) = keys[0]
        assert bucket == 4
        assert leaf_specs == (((img, img, ch), "|u1"),)
        assert pool._key_bytes[keys[0]] == raw_buf_bytes
        assert pool.staging_bytes == raw_buf_bytes * allocations
        assert engine.metrics.staging_bytes == pool.staging_bytes
    # sequential awaited windows recycle buffers: the no-growth bound
    # is the pool cap (depth+1 per key), not per-window growth
    assert allocations <= pool.max_per_key, (
        f"{allocations} uint8 staging allocations for {n_windows} windows"
    )
    assert engine.metrics.windows.total == n_windows
    # and what went over the wire was the raw uint8 footprint: one
    # byte per element, a quarter of what the same elements cost in f32
    assert engine.metrics.h2d_bytes.total == n_windows * raw_buf_bytes
