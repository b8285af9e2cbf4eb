"""PCA/ZCA tests (reference: PCASuite, ZCAWhitenerSuite)."""

import numpy as np
import pytest

from keystone_tpu.ops.learning import (
    ApproximatePCAEstimator,
    ColumnPCAEstimator,
    DistributedColumnPCAEstimator,
    DistributedPCAEstimator,
    LocalColumnPCAEstimator,
    PCAEstimator,
    ZCAWhitenerEstimator,
)
from keystone_tpu.parallel.dataset import Dataset


def _random_lowrank(n, d, r, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((n, r)) @ rng.standard_normal((r, d))
        + 0.01 * rng.standard_normal((n, d))
    ).astype(np.float32)


def _np_pca(X, dims):
    Xc = X - X.mean(0)
    _, _, vt = np.linalg.svd(Xc, full_matrices=False)
    V = vt.T
    col_max = V.max(0)
    abs_max = np.abs(V).max(0)
    V = V * np.where(col_max == abs_max, 1.0, -1.0)
    return V[:, :dims]


def test_local_pca_matches_numpy():
    X = _random_lowrank(80, 12, 5)
    t = PCAEstimator(4).fit(Dataset.of(X))
    np.testing.assert_allclose(
        np.asarray(t.pca_mat), _np_pca(X, 4), atol=2e-3
    )


def test_distributed_pca_matches_local(mesh8):
    X = _random_lowrank(96, 10, 4, seed=1)
    local = PCAEstimator(3).fit(Dataset.of(X))
    dist = DistributedPCAEstimator(3).fit(Dataset.of(X).shard())
    np.testing.assert_allclose(
        np.abs(np.asarray(dist.pca_mat)),
        np.abs(np.asarray(local.pca_mat)),
        atol=5e-3,
    )


def test_approximate_pca_subspace(mesh8):
    X = _random_lowrank(120, 16, 3, seed=2)
    exact = _np_pca(X, 3)
    approx = np.asarray(ApproximatePCAEstimator(3, seed=0).fit(Dataset.of(X)).pca_mat)
    # compare subspaces via principal angles
    s = np.linalg.svd(exact.T @ approx, compute_uv=False)
    assert s.min() > 0.99


def test_column_pca_on_matrix_items():
    rng = np.random.default_rng(3)
    mats = [rng.standard_normal((8, 20)).astype(np.float32) for _ in range(5)]
    t = LocalColumnPCAEstimator(4).fit(Dataset.from_items(mats))
    out = t.apply(mats[0])
    assert np.asarray(out).shape == (4, 20)


def test_column_pca_optimize_picks_an_option(mesh8):
    rng = np.random.default_rng(4)
    mats = [rng.standard_normal((8, 10)).astype(np.float32) for _ in range(4)]
    est = ColumnPCAEstimator(4)
    chosen = est.optimize([Dataset.from_items(mats)], 4)
    assert isinstance(
        chosen, (LocalColumnPCAEstimator, DistributedColumnPCAEstimator)
    )


def test_zca_whitening_decorrelates():
    rng = np.random.default_rng(5)
    X = (rng.standard_normal((500, 6)) @ rng.standard_normal((6, 6))).astype(
        np.float32
    )
    w = ZCAWhitenerEstimator(eps=1e-6).fit(Dataset.of(X))
    out = np.asarray(w.apply(X))
    cov = out.T @ out / (out.shape[0] - 1)
    np.testing.assert_allclose(cov, np.eye(6), atol=0.15)
    # whitener is symmetric (ZCA, not PCA whitening)
    np.testing.assert_allclose(
        np.asarray(w.whitener), np.asarray(w.whitener).T, atol=1e-4
    )


def test_column_pca_fit_stays_on_the_device_and_counts():
    """A column PCA over an array of (d, m) matrices takes its columns
    without a host copy (``_columns_dataset``), fits from the centered
    Gram, matches the SVD of the centered sample up to sign, and leaves
    ``pca.fit`` and ``keystone_pca_fits_total`` behind."""
    import jax.numpy as jnp

    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.observability.tracing import (
        disable_tracing, enable_tracing,
    )
    from keystone_tpu.ops.learning.pca import _columns_dataset

    rng = np.random.default_rng(4)
    scale = np.linspace(3.0, 0.5, 6)[:, None]
    mats = (rng.standard_normal((5, 6, 40)) * scale).astype(np.float32)
    ds = Dataset.from_array(jnp.asarray(mats))
    cols = _columns_dataset(ds)
    np.testing.assert_array_equal(
        np.asarray(cols.array()),
        np.concatenate([m.T for m in mats]))
    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        t = LocalColumnPCAEstimator(3).fit(ds)
        names = [s.name for s in tr.recent()]
        fits = sum(
            s.value for f in get_global_registry().collect()
            if f.name == "keystone_pca_fits_total"
            for s in f.samples if s.suffix == "")
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()
    x = np.concatenate([m.T for m in mats]).astype(np.float64)
    _, _, vt = np.linalg.svd(x - x.mean(0), full_matrices=False)
    got = np.asarray(t.pca_mat)
    assert got.shape == (6, 3)
    np.testing.assert_allclose(np.abs(got), np.abs(vt[:3].T), atol=2e-4)
    assert names.count("pca.fit") == 1 and fits == 1
    # one machine has nothing to distribute over
    assert isinstance(ColumnPCAEstimator(3, num_machines=1).optimize(
        [Dataset.from_items(list(mats))], 5), LocalColumnPCAEstimator)
