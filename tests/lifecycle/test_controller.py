"""LifecycleController over a real (tiny) Gateway: end-to-end
promotion, bitwise-identical rollback, poisoned-refit auto-rollback
within one policy tick, and refit-vs-swap concurrency safety."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.gateway import Gateway
from keystone_tpu.lifecycle.controller import LifecycleController
from keystone_tpu.lifecycle.policy import PromotionConfig
from keystone_tpu.lifecycle.teacher import teacher_labels
from keystone_tpu.loadgen import faults
from keystone_tpu.serving.demo_model import affine_head, build_split_pipeline

D, HIDDEN, DEPTH = 6, 8, 2
HEAD_SEED = 55

CFG = PromotionConfig(
    min_shadow_pairs=2,
    min_canary_requests=2,
    promote_after_healthy_ticks=1,
)


@pytest.fixture(scope="module")
def split():
    return build_split_pipeline(d=D, hidden=HIDDEN, depth=DEPTH, seed=1)


def _labeled(n, seed=21):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, D)).astype(np.float32)
    Y = teacher_labels(X, D, HIDDEN, DEPTH, seed=1, head_seed=HEAD_SEED)
    return X, Y


def _gateway(split):
    base, W0, b0 = split
    stale = base.and_then(affine_head(W0, b0))
    return base, Gateway(
        stale, buckets=(4,), n_lanes=1, max_delay_ms=1.0,
        warmup_example=jnp.zeros((D,), jnp.float32),
        name="test-lifecycle",
    )


def _controller(gw, base, **kw):
    kw.setdefault("config", CFG)
    kw.setdefault("canary_fraction", 0.5)
    kw.setdefault("min_refit_samples", 32)
    return LifecycleController(
        gw, base=base, head_builder=affine_head,
        feature_dim=HIDDEN, out_dim=D, name="m", **kw
    )


def _drive_to(gw, ctrl, target, examples, max_ticks=25):
    """Tick while feeding live traffic until the state machine reaches
    ``target`` (shadow pairs and canary requests both need real
    requests flowing through the pool hooks)."""
    status = ctrl.status()
    for _ in range(max_ticks):
        if status["state"] == target:
            return status
        for i in range(4):
            gw.predict(examples[i % len(examples)]).result(timeout=30)
        time.sleep(0.1)  # let shadow/canary completion callbacks land
        status = ctrl.tick()
    return status


def test_promote_and_bitwise_rollback(split):
    base, gw = _gateway(split)
    rng = np.random.default_rng(3)
    examples = rng.standard_normal((8, D)).astype(np.float32)
    probe = examples[0]
    with gw:
        ctrl = _controller(gw, base)
        try:
            before = np.asarray(gw.predict(probe).result(timeout=30))
            ctrl.add_feedback(*_labeled(200))
            status = ctrl.tick()  # solves v1, arms its shadow
            assert status["state"] == "shadow"
            assert status["version"] == 1
            status = _drive_to(gw, ctrl, "promoted", examples)
            assert status["state"] == "promoted", status
            assert status["promotions"] == 1
            # the candidate beat the stale incumbent on held-out labels
            assert (status["errors"]["candidate"]
                    < status["errors"]["incumbent"])
            after = np.asarray(gw.predict(probe).result(timeout=30))
            assert not np.array_equal(before, after)
            # the promoted model actually tracks the teacher now
            want = teacher_labels(
                probe[None], D, HIDDEN, DEPTH, seed=1,
                head_seed=HEAD_SEED,
            )[0]
            assert float(np.abs(after - want).max()) < 0.05
            # operator rollback un-promotes: the retained incumbent
            # serves BITWISE-identical outputs again
            status = ctrl.force_rollback("test")
            assert status["state"] == "rolled_back"
            restored = np.asarray(gw.predict(probe).result(timeout=30))
            np.testing.assert_array_equal(restored, before)
        finally:
            ctrl.close()


def test_poisoned_refit_rolls_back_within_one_tick(split):
    base, gw = _gateway(split)
    probe = np.linspace(-1, 1, D).astype(np.float32)
    with gw:
        ctrl = _controller(gw, base)
        try:
            before = np.asarray(gw.predict(probe).result(timeout=30))
            faults.get_injector().arm(
                "lifecycle.refit.poison", count=100
            )
            ctrl.add_feedback(*_labeled(200))
            status = ctrl.tick()  # solves the poisoned v1
            assert status["state"] == "shadow"
            status = ctrl.tick()  # the accuracy gate catches it
            assert status["state"] == "rolled_back", status
            assert status["last_reason"] == "accuracy"
            # the incumbent never stopped serving, bit for bit
            after = np.asarray(gw.predict(probe).result(timeout=30))
            np.testing.assert_array_equal(after, before)
            # the tainted accumulation window was discarded: the next
            # cycle does not resurrect the poisoned normal equations
            assert status["refit"]["accumulated"] == 0
        finally:
            ctrl.close()


def test_rollback_discard_allows_clean_recovery(split):
    """After a poisoned rollback, clean feedback must produce a
    promotable candidate — the poison must not linger."""
    base, gw = _gateway(split)
    rng = np.random.default_rng(4)
    examples = rng.standard_normal((8, D)).astype(np.float32)
    with gw:
        ctrl = _controller(gw, base)
        try:
            faults.get_injector().arm("lifecycle.refit.poison", count=100)
            ctrl.add_feedback(*_labeled(200))
            ctrl.tick()
            status = ctrl.tick()
            assert status["state"] == "rolled_back"
            faults.get_injector().disarm("lifecycle.refit.poison")
            ctrl.add_feedback(*_labeled(200, seed=33))
            status = ctrl.tick()
            assert status["state"] == "shadow"
            assert status["version"] == 2
            status = _drive_to(gw, ctrl, "promoted", examples)
            assert status["state"] == "promoted", status
        finally:
            ctrl.close()


def test_no_candidate_until_min_samples(split):
    base, gw = _gateway(split)
    with gw:
        ctrl = _controller(gw, base, min_refit_samples=500)
        try:
            ctrl.add_feedback(*_labeled(100))
            status = ctrl.tick()
            assert status["state"] == "idle"
            assert status["version"] == 0
        finally:
            ctrl.close()


def test_concurrent_refit_vs_swap(split):
    """Policy ticks (candidate builds, engine swaps on promotion) and
    forced pool rebuckets race without deadlock or request failures —
    the swap lock serializes the engine rotations."""
    base, gw = _gateway(split)
    rng = np.random.default_rng(5)
    examples = rng.standard_normal((8, D)).astype(np.float32)
    with gw:
        ctrl = _controller(gw, base)
        errs = []

        def ticker():
            try:
                for i in range(6):
                    ctrl.add_feedback(*_labeled(64, seed=100 + i))
                    ctrl.tick()
                    for j in range(2):
                        gw.predict(examples[j]).result(timeout=30)
            except Exception as e:  # pragma: no cover - the assert
                errs.append(e)

        def swapper():
            try:
                for _ in range(4):
                    gw.rebucket(force=True)
            except Exception as e:  # pragma: no cover - the assert
                errs.append(e)

        try:
            threads = [
                threading.Thread(target=ticker),
                threading.Thread(target=swapper),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "refit-vs-swap deadlock"
            assert not errs, errs
            out = gw.predict(examples[0]).result(timeout=30)
            assert np.asarray(out).shape == (D,)
        finally:
            ctrl.close()
