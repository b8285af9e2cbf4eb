"""The streaming-refit accumulator: one-pass normal equations over
feedback chunks (chunk-size independent), exact head recovery,
holdout separation, snapshot/restore, and the poison fault point."""

import numpy as np
import pytest

from keystone_tpu.lifecycle.refit import RefitAccumulator
from keystone_tpu.lifecycle.teacher import teacher_labels
from keystone_tpu.loadgen import faults
from keystone_tpu.serving.demo_model import affine_head, build_split_pipeline

D, HIDDEN, DEPTH = 6, 8, 2
HEAD_SEED = 99


@pytest.fixture(scope="module")
def split():
    base, W, b = build_split_pipeline(
        d=D, hidden=HIDDEN, depth=DEPTH, seed=3
    )
    return base, W, b


def _labeled(n, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, D)).astype(np.float32)
    Y = teacher_labels(X, D, HIDDEN, DEPTH, seed=3, head_seed=HEAD_SEED)
    return X, Y


def test_recovers_teacher_head(split):
    base, W0, b0 = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, lam=1e-5, chunk=16
    )
    X, Y = _labeled(600)
    acc.add(X, Y)
    W, b = acc.solve()
    candidate = base.and_then(affine_head(W, b))
    stale = base.and_then(affine_head(W0, b0))
    cand_err, stale_err = acc.holdout_errors(candidate, stale)
    assert cand_err is not None and stale_err is not None
    assert cand_err < stale_err * 1e-2, (cand_err, stale_err)


def test_chunk_size_independence(split):
    """The core one-pass property: folding the same rows in different
    chunkings solves to the same head — so 'streaming refit' is
    accumulate + re-solve regardless of how feedback arrived."""
    base, _, _ = split
    X, Y = _labeled(300)
    solved = []
    for chunk, batches in ((8, 1), (64, 3), (300, 5)):
        acc = RefitAccumulator(
            base, feature_dim=HIDDEN, out_dim=D, lam=1e-4, chunk=chunk
        )
        for part_x, part_y in zip(
            np.array_split(X, batches), np.array_split(Y, batches)
        ):
            acc.add(part_x, part_y)
        W, b = acc.solve()
        solved.append((np.asarray(W), np.asarray(b)))
    for W, b in solved[1:]:
        np.testing.assert_allclose(W, solved[0][0], atol=1e-4)
        np.testing.assert_allclose(b, solved[0][1], atol=1e-4)


def test_holdout_separation(split):
    """Every holdout_every-th row is diverted to the held-out buffer
    and never folded into the normal equations."""
    base, _, _ = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, chunk=16, holdout_every=4
    )
    X, Y = _labeled(100)
    acc.add(X, Y)
    assert acc.n_holdout == 25
    assert acc.n_accumulated == 75
    assert acc.n_holdout + acc.n_accumulated == 100


def test_holdout_cap(split):
    base, _, _ = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, chunk=32,
        holdout_every=2, holdout_cap=10,
    )
    X, Y = _labeled(200)
    acc.add(X, Y)
    assert acc.n_holdout == 10
    assert acc.n_accumulated == 190


def test_solve_requires_samples(split):
    base, _, _ = split
    acc = RefitAccumulator(base, feature_dim=HIDDEN, out_dim=D)
    with pytest.raises(RuntimeError):
        acc.solve()


def test_snapshot_restore_discards_later_chunks(split):
    base, _, _ = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, lam=1e-4, chunk=16
    )
    X, Y = _labeled(200)
    acc.add(X, Y)
    W1, b1 = acc.solve()
    snap = acc.snapshot()
    # fold garbage, then restore: the solve must match the snapshot
    Xg, Yg = _labeled(100, seed=8)
    acc.add(Xg, -np.ones_like(Yg) * 0.9)
    W2, _ = acc.solve()
    assert not np.allclose(np.asarray(W2), np.asarray(W1), atol=1e-3)
    acc.restore(snap)
    W3, b3 = acc.solve()
    np.testing.assert_array_equal(np.asarray(W3), np.asarray(W1))
    np.testing.assert_array_equal(np.asarray(b3), np.asarray(b1))


def test_poison_fault_corrupts_solve_but_not_holdout(split):
    """lifecycle.refit.poison: armed, the accumulated chunks' targets
    are corrupted BEFORE they fold into the normal equations — the
    solved candidate is garbage, while the held-out buffer stays
    clean so the accuracy gate catches exactly this."""
    base, W0, b0 = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, lam=1e-5, chunk=16
    )
    X, Y = _labeled(400)
    faults.get_injector().arm("lifecycle.refit.poison", count=100)
    try:
        acc.add(X, Y)
    finally:
        faults.get_injector().disarm("lifecycle.refit.poison")
    W, b = acc.solve()
    poisoned = base.and_then(affine_head(W, b))
    stale = base.and_then(affine_head(W0, b0))
    # the holdout rows were diverted before the poison site, so the
    # comparison is against CLEAN labels: the poisoned candidate must
    # look much worse than even the stale incumbent
    cand_err, stale_err = acc.holdout_errors(poisoned, stale)
    assert cand_err > stale_err * 1.5, (cand_err, stale_err)


def test_poison_fires_and_counts(split):
    base, _, _ = split
    acc = RefitAccumulator(
        base, feature_dim=HIDDEN, out_dim=D, chunk=16
    )
    inj = faults.get_injector()
    inj.arm("lifecycle.refit.poison", count=2)
    X, Y = _labeled(64)
    acc.add(X, Y)
    assert inj.status()["fired_total"].get(
        "lifecycle.refit.poison", 0
    ) >= 1
