"""Warm engine pool: prebuilt-engine swap (build outside the pool
lock), background next-generation rotation, and the gateway lifecycle
riding the persistent compile cache end to end."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from compile_cache_fixtures import scratch_compile_cache

from keystone_tpu.gateway.lifecycle import Gateway
from keystone_tpu.gateway.metrics import GatewayMetrics
from keystone_tpu.gateway.pool import EnginePool
from keystone_tpu.observability.registry import MetricsRegistry

from gateway_fixtures import D, batch, reference

WARM = jnp.zeros((D,), jnp.float32)


def make_pool(fitted, n_lanes=2, buckets=(4,)):
    return EnginePool(
        lambda name: fitted.compiled(buckets=buckets, name=name),
        n_lanes,
        name="warmpool-test",
        max_delay_ms=2.0,
        metrics=GatewayMetrics(
            registry=MetricsRegistry(), gateway="warmpool-test"
        ),
    )


def test_pool_swap_accepts_prebuilt_engines(fitted):
    pool = make_pool(fitted, n_lanes=2)
    with pool:
        prebuilt = [
            fitted.compiled(buckets=(8,), name=pool.lane_name(i))
            for i in range(2)
        ]
        for eng in prebuilt:
            eng.warmup(example=WARM)
        old = pool.swap(engines=prebuilt)
        assert len(old) == 2
        assert [l.engine for l in pool.lanes] == prebuilt
        xs = batch(6, seed=7)
        futs = [pool.submit(x) for x in xs]
        rows = np.stack([np.asarray(f.result(timeout=30)) for f in futs])
    np.testing.assert_allclose(
        rows, reference(fitted, xs), rtol=1e-5, atol=1e-6
    )


def test_pool_swap_rejects_wrong_prebuilt_count(fitted):
    pool = make_pool(fitted, n_lanes=2)
    with pool:
        lonely = fitted.compiled(buckets=(8,), name="only-one")
        with pytest.raises(ValueError, match="one prebuilt engine"):
            pool.swap(engines=[lonely])
        # the failed swap left the original engines serving
        assert pool.submit(batch(1)[0]).result(timeout=30) is not None


def test_background_swap_rotates_under_traffic(fitted):
    with Gateway(
        fitted, buckets=(4,), n_lanes=2, max_delay_ms=2.0,
        warmup_example=WARM, name="bg-swap",
        registry=MetricsRegistry(),
    ) as gw:
        stop = threading.Event()
        failures = []

        def client():
            while not stop.is_set():
                try:
                    gw.predict(batch(1, seed=3)[0]).result(timeout=30)
                except Exception as e:  # pragma: no cover - fail loud
                    failures.append(e)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        fut = gw.swap_engines((4, 8), background=True)
        assert fut.result(timeout=60) is True
        stop.set()
        t.join(timeout=30)
        assert gw.buckets == (4, 8)
        assert not failures, f"requests failed across the swap: {failures}"
        # traffic still resolves on the rotated engines
        out = gw.predict(batch(1, seed=4)[0]).result(timeout=30)
        assert np.asarray(out).shape == (3,)


def test_background_swap_after_close_is_a_noop(fitted):
    gw = Gateway(
        fitted, buckets=(4,), n_lanes=1, max_delay_ms=2.0,
        warmup_example=WARM, name="bg-closed",
        registry=MetricsRegistry(),
    )
    gw.close()
    fut = gw.swap_engines((4, 8), background=True)
    assert fut.result(timeout=60) is False  # dropped, nothing rotated


def test_gateway_lanes_and_next_generation_ride_the_compile_cache(
    fitted, tmp_path
):
    """A restarted gateway: with generation 0's programs on the disk,
    every lane engine of a fresh gateway reads its programs off the
    disk, the next generation a background swap builds runs no XLA
    compile either, and the lanes serve the reference outputs."""
    with scratch_compile_cache(tmp_path / "cache") as log:
        with Gateway(
            fitted, buckets=(4,), n_lanes=2, max_delay_ms=2.0,
            warmup_example=WARM, name="cc-gw-0",
            registry=MetricsRegistry(),
        ):
            pass

        jax.clear_caches()
        log.mark()
        with Gateway(
            fitted, buckets=(4,), n_lanes=2, max_delay_ms=2.0,
            warmup_example=WARM, name="cc-gw-1",
            registry=MetricsRegistry(),
        ) as gw:
            compiles = log.since_mark()
            assert "jit(staged)" in {name for name, _ in compiles}
            assert {outcome for _, outcome in compiles} == {"cache_hit"}
            for lane in gw.pool.lanes:
                # one trace per bucket, its executable off the disk
                assert lane.engine.metrics.compile_count == 1
            old = [lane.engine for lane in gw.pool.lanes]

            log.mark()
            fut = gw.swap_engines((4,), background=True)
            assert fut.result(timeout=60) is True
            assert {outcome for _, outcome in log.since_mark()} <= {
                "cache_hit"
            }
            for lane, before in zip(gw.pool.lanes, old):
                assert lane.engine is not before
                assert lane.engine.metrics.compile_count == 1
            xs = batch(3, seed=9)
            rows = np.stack([
                np.asarray(gw.predict(x).result(timeout=30)) for x in xs
            ])
    np.testing.assert_allclose(
        rows, reference(fitted, xs), rtol=1e-5, atol=1e-6
    )
