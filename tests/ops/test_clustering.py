"""KMeans++/GMM tests (reference: KMeansPlusPlusSuite,
GaussianMixtureModelSuite)."""

import numpy as np
import pytest

from keystone_tpu.ops.learning import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
    KMeansPlusPlusEstimator,
)
from keystone_tpu.parallel.dataset import Dataset


def _blobs(n_per, centers, spread=0.1, seed=0):
    rng = np.random.default_rng(seed)
    xs = [
        c + spread * rng.standard_normal((n_per, len(c)))
        for c in centers
    ]
    return np.concatenate(xs).astype(np.float32)


def test_kmeans_recovers_blobs():
    centers = [np.array([0.0, 0.0]), np.array([5.0, 5.0]), np.array([-5.0, 5.0])]
    X = _blobs(60, centers, seed=0)
    model = KMeansPlusPlusEstimator(3, 20, seed=0).fit(Dataset.of(X))
    means = np.asarray(model.means)
    # each true center has a learned center nearby
    for c in centers:
        assert np.min(np.linalg.norm(means - c, axis=1)) < 0.5


def test_kmeans_assignment_one_hot():
    X = _blobs(10, [np.array([0.0, 0.0]), np.array([9.0, 9.0])], seed=1)
    model = KMeansPlusPlusEstimator(2, 5, seed=0).fit(Dataset.of(X))
    assign = np.asarray(model.apply_batch(Dataset.of(X)).array())
    assert assign.shape == (20, 2)
    np.testing.assert_allclose(assign.sum(1), np.ones(20))
    assert set(np.unique(assign)) <= {0.0, 1.0}


def test_gmm_em_recovers_blobs():
    centers = [np.array([0.0, 0.0]), np.array([6.0, 6.0])]
    X = _blobs(200, centers, spread=0.5, seed=2)
    gmm = GaussianMixtureModelEstimator(
        2, max_iterations=50, min_cluster_size=10, seed=0
    ).fit(Dataset.of(X))
    mu = np.asarray(gmm.means).T  # (k, d)
    for c in centers:
        assert np.min(np.linalg.norm(mu - c, axis=1)) < 0.5
    # posteriors are a (thresholded) distribution
    q = np.asarray(gmm.apply_batch(Dataset.of(X)).array())
    np.testing.assert_allclose(q.sum(1), np.ones(len(X)), atol=1e-5)


def test_gmm_csv_load(tmp_path):
    means = np.array([[0.0, 1.0], [2.0, 3.0]])  # (d=2, k=2)
    variances = np.ones((2, 2))
    weights = np.array([0.4, 0.6])
    mf, vf, wf = (
        tmp_path / "m.csv", tmp_path / "v.csv", tmp_path / "w.csv"
    )
    np.savetxt(mf, means, delimiter=",")
    np.savetxt(vf, variances, delimiter=",")
    np.savetxt(wf, weights, delimiter=",")
    gmm = GaussianMixtureModel.load(str(mf), str(vf), str(wf))
    assert gmm.k == 2 and gmm.dim == 2
    out = gmm.apply(np.array([0.0, 2.0], np.float32))
    assert out.shape == (2,)


def _stepped_em(est, X):
    """The EM the estimator replaced, stepped from the host with the
    posteriors written out whole (GaussianMixtureModelEstimator.scala's
    loop): (means (k, d), variances, weights, rounds begun, reason), from
    the estimator's own start."""
    import jax.numpy as jnp

    X = np.asarray(X, np.float64)
    n, d = X.shape
    mu, var, w, var_lb, _ = (
        None if a is None else np.asarray(a, np.float64)
        for a in est.initialize(jnp.asarray(X, jnp.float32).T))
    prev, rounds, reason = None, 0, "max_iter"
    for _ in range(est.max_iterations):
        rounds += 1
        llh = (-0.5 * (X * X) @ (1 / var).T + X @ (mu / var).T
               - 0.5 * np.sum(mu * mu / var, 1)
               - 0.5 * np.sum(np.log(2 * np.pi * var), 1) + np.log(w))
        top = llh.max(1, keepdims=True)
        e = np.exp(llh - top)
        cost = float(np.mean(top[:, 0] + np.log(e.sum(1))))
        if prev is not None and cost - prev < est.stop_tolerance * abs(prev):
            reason = "tolerance"
            break
        prev = cost
        q = e / e.sum(1, keepdims=True)
        q = np.where(q > est.weight_threshold, q, 0.0)
        q /= q.sum(1, keepdims=True)
        q_sum = q.sum(0)
        if np.any(q_sum < est.min_cluster_size):
            reason = "cluster_floor"
            break
        w = q_sum / n
        mu = (q.T @ X) / q_sum[:, None]
        var = np.maximum((q.T @ (X * X)) / q_sum[:, None] - mu * mu, var_lb)
    return mu, var, w, rounds, reason


@pytest.mark.parametrize("case", ["tolerance", "max_iter", "cluster_floor"])
def test_device_em_matches_the_stepped_em(case):
    """The one-program EM (blocked statistics, no posteriors written
    out) against the host-stepped EM it replaced, from the same start:
    the model, the rounds begun and the reason it stopped."""
    from keystone_tpu.ops.learning import GaussianMixtureModelEstimator

    rng = np.random.default_rng(0)
    centers = np.asarray([[0.0, 0.0], [5.0, 5.0], [-5.0, 5.0]], np.float32)
    X = np.concatenate([
        rng.standard_normal((120, 2)).astype(np.float32) * 0.4 + c
        for c in centers
    ])
    kwargs = {
        "tolerance": dict(k=3, max_iterations=30, min_cluster_size=5),
        "max_iter": dict(k=3, max_iterations=2, min_cluster_size=5,
                         stop_tolerance=-1.0),
        "cluster_floor": dict(k=3, max_iterations=30,
                              min_cluster_size=200),
    }[case]
    est = GaussianMixtureModelEstimator(seed=1, **kwargs)
    got = est.fit(X)
    mu, var, w, rounds, reason = _stepped_em(est, X)
    assert got.fit_info["reason"] == reason == case
    assert got.fit_info["iterations"] == rounds
    np.testing.assert_allclose(np.asarray(got.means).T, mu, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got.variances).T, var, rtol=1e-3,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.weights), w, atol=1e-5)
    if case == "tolerance":  # and it recovers the true centers
        np.testing.assert_allclose(
            np.sort(np.asarray(got.means).T, axis=0),
            np.sort(centers, axis=0), atol=0.3)


def test_device_kmeans_pp_draw_is_the_host_loops():
    """The k-means++ start drawn on the device from the host generator's
    uniforms takes the seeds ``KMeansPlusPlusEstimator``'s host loop
    takes, and a random start needs none."""
    import jax.numpy as jnp

    from keystone_tpu.ops.learning import GaussianMixtureModelEstimator
    from keystone_tpu.ops.learning.gmm import RANDOM_INITIALIZATION

    rng = np.random.default_rng(3)
    X = rng.standard_normal((400, 5)).astype(np.float64)
    k, seed = 7, 11
    est = GaussianMixtureModelEstimator(k=k, seed=seed)
    seeds = np.asarray(est.initialize(jnp.asarray(X, jnp.float32).T)[4])
    host = np.random.default_rng(seed)
    want, half, dist = [int(host.integers(0, len(X)))], \
        0.5 * np.sum(X * X, axis=1), None
    for j in range(k - 1):
        c = X[want[j]]
        new = half - X @ c + 0.5 * (c @ c)
        dist = new if dist is None else np.minimum(new, dist)
        p = np.maximum(dist, 0.0)
        want.append(int(host.choice(len(X), p=p / p.sum())))
    assert seeds.tolist() == want
    rand = GaussianMixtureModelEstimator(
        k=k, seed=seed, initialization_method=RANDOM_INITIALIZATION)
    assert rand.initialize(jnp.asarray(X, jnp.float32).T)[4] is None
    assert rand.fit(X).fit_info["seeds"] is None
