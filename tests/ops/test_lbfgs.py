"""L-BFGS tests (reference: LBFGSSuite — distributed vs local solutions)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.learning import (
    DenseLBFGSwithL2,
    SparseLBFGSwithL2,
)
from keystone_tpu.ops.util.nodes import Sparsify
from keystone_tpu.parallel.dataset import Dataset


def test_dense_lbfgs_recovers_ols(mesh8):
    rng = np.random.default_rng(0)
    A = rng.standard_normal((128, 10)).astype(np.float32)
    W_true = rng.standard_normal((10, 3)).astype(np.float32)
    b = A @ W_true + 0.7
    est = DenseLBFGSwithL2(num_iterations=60, reg_param=0.0)
    model = est.fit(Dataset.of(A).shard(), Dataset.of(b).shard())
    pred = np.asarray(model.apply_batch(Dataset.of(A)).array())
    assert np.abs(pred - b).max() < 0.05


def test_dense_lbfgs_l2_matches_ridge(mesh8):
    rng = np.random.default_rng(1)
    A = rng.standard_normal((200, 6)).astype(np.float32)
    b = rng.standard_normal((200, 2)).astype(np.float32)
    lam = 0.1
    n = A.shape[0]
    est = DenseLBFGSwithL2(
        num_iterations=100, reg_param=lam, fit_intercept=False,
        convergence_tol=1e-10,
    )
    model = est.fit(Dataset.of(A), Dataset.of(b))
    # objective: ||AW-b||^2/(2n) + lam/2 ||W||^2  =>  (A'A/n + lam I) W = A'b/n
    expect = np.linalg.solve(A.T @ A / n + lam * np.eye(6), A.T @ b / n)
    np.testing.assert_allclose(np.asarray(model.W), expect, atol=5e-3)


def test_sparse_lbfgs_runs(mesh8):
    rng = np.random.default_rng(2)
    A = (rng.standard_normal((64, 8)) * (rng.random((64, 8)) < 0.3)).astype(
        np.float32
    )
    W_true = rng.standard_normal((8, 2)).astype(np.float32)
    b = (A @ W_true).astype(np.float32)
    sparse_ds = Sparsify().apply_batch(Dataset.of(A))
    est = SparseLBFGSwithL2(num_iterations=60)
    model = est.fit(sparse_ds, Dataset.of(b))
    pred = np.asarray(model.apply_batch(sparse_ds).array())
    assert np.abs(pred - b).max() < 0.05


def test_lbfgs_weight():
    assert DenseLBFGSwithL2(num_iterations=20).weight == 21


def test_device_lbfgs_matches_host_driver_least_squares():
    import dataclasses as dc

    rng = np.random.default_rng(11)
    n, d, k = 400, 24, 3
    X = rng.standard_normal((n, d)).astype(np.float32)
    W_true = rng.standard_normal((d, k)).astype(np.float32)
    Y = X @ W_true
    Xd = Dataset.from_array(jnp.asarray(X))
    Yd = Dataset.from_array(jnp.asarray(Y))
    base = DenseLBFGSwithL2(reg_param=1e-4, num_iterations=40,
                            fit_intercept=False)
    m_dev = dc.replace(base, driver="device").fit(Xd, Yd)
    m_host = dc.replace(base, driver="host").fit(Xd, Yd)
    # both recover the generating model; drivers agree to optimizer noise
    assert np.abs(np.asarray(m_dev.W) - W_true).max() < 5e-2
    assert np.abs(np.asarray(m_dev.W) - np.asarray(m_host.W)).max() < 5e-2


def test_device_lbfgs_logistic_regression_learns():
    from keystone_tpu.ops.learning import LogisticRegressionEstimator

    rng = np.random.default_rng(12)
    n, d, k = 600, 10, 3
    centers = rng.standard_normal((k, d)).astype(np.float32) * 3
    y = rng.integers(0, k, n).astype(np.int32)
    X = centers[y] + rng.standard_normal((n, d)).astype(np.float32)
    Xd = Dataset.from_array(jnp.asarray(X))
    yd = Dataset.from_array(jnp.asarray(y))
    model = LogisticRegressionEstimator(
        num_classes=k, num_iters=30, driver="device"
    ).fit(Xd, yd)
    preds = np.asarray(model.apply_batch(Xd).padded())
    assert (preds == y).mean() > 0.9
    host = LogisticRegressionEstimator(
        num_classes=k, num_iters=30, driver="host"
    ).fit(Xd, yd)
    hp = np.asarray(host.apply_batch(Xd).padded())
    assert (preds == hp).mean() > 0.95


def test_device_lbfgs_line_search_failure_terminates():
    """A pathological objective whose 'gradient' points uphill everywhere
    makes every Armijo trial fail; the driver must stop cleanly at w0
    rather than loop or return NaN."""
    from keystone_tpu.ops.learning.lbfgs import run_lbfgs_device

    def bad_vg(w):
        # claims descent direction -g, but f grows along it
        return jnp.sum(w * w) + 1.0, -jnp.ones_like(w)

    w, _, _ = run_lbfgs_device(bad_vg, jnp.zeros((4, 2)), 10)
    assert np.isfinite(np.asarray(w)).all()
    # at f32 resolution the backtracked step may be accepted at rounding
    # noise; the property that matters is no runaway along the bogus
    # direction
    assert np.abs(np.asarray(w)).max() < 1e-3


def test_device_lbfgs_convergence_tol_is_traced():
    """Different tolerances reuse one compiled program (tol is a traced
    argument, not a static one)."""
    from keystone_tpu.ops.learning.lbfgs import (
        _lbfgs_device_run, run_lbfgs_device,
    )

    def quad_vg(w, A):
        return 0.5 * jnp.sum((A @ w) * w), A @ w

    A = jnp.eye(8) * jnp.arange(1.0, 9.0)
    w0 = jnp.ones((8,))
    before = _lbfgs_device_run._cache_size()
    w1, _, _ = run_lbfgs_device(quad_vg, w0, 50, convergence_tol=1e-2,
                                 data=(A,))
    w2, _, _ = run_lbfgs_device(quad_vg, w0, 50, convergence_tol=1e-8,
                                 data=(A,))
    after = _lbfgs_device_run._cache_size()
    assert after - before == 1  # one compile for both tolerances
    # tighter tolerance gets at least as close to the optimum (0)
    assert np.abs(np.asarray(w2)).max() <= np.abs(np.asarray(w1)).max() + 1e-6
