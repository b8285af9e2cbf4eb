"""Fused (Pallas) vs unfused Fisher-vector path equivalence, and the
k-threshold physical choice (reference: FisherVector.scala:84-94,
EncEvalSuite fixture constant)."""

import numpy as np
import pytest
import jax.numpy as jnp

from keystone_tpu.ops.images.fisher_vector import (
    FisherVector,
    FisherVectorFused,
    GMMFisherVectorEstimator,
)
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
from keystone_tpu.parallel.dataset import Dataset


def _random_model(d=16, k=32, seed=0):
    rng = np.random.default_rng(seed)
    return GaussianMixtureModel(
        jnp.asarray(rng.standard_normal((d, k)).astype(np.float32)),
        jnp.asarray((rng.random((d, k)) + 0.5).astype(np.float32)),
        jnp.asarray(rng.dirichlet(np.ones(k)).astype(np.float32)),
    )


def test_fused_matches_unfused_single():
    gmm = _random_model()
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 300)).astype(np.float32)
    fv_plain = np.asarray(FisherVector(gmm).apply(x))
    fv_fused = np.asarray(FisherVectorFused(gmm).apply(x))
    assert fv_plain.shape == fv_fused.shape == (16, 64)
    np.testing.assert_allclose(fv_fused, fv_plain, rtol=1e-3, atol=1e-4)


def test_fused_matches_unfused_batch():
    gmm = _random_model(d=8, k=32, seed=2)
    rng = np.random.default_rng(3)
    batch = rng.standard_normal((4, 8, 200)).astype(np.float32)
    ds = Dataset.from_array(jnp.asarray(batch))
    out_plain = np.asarray(FisherVector(gmm).apply_batch(ds).padded())
    out_fused = np.asarray(FisherVectorFused(gmm).apply_batch(ds).padded())
    np.testing.assert_allclose(out_fused, out_plain, rtol=1e-3, atol=1e-4)


def test_auto_interpret_parity_vs_numpy_reference():
    """``fisher_vector_stats_pallas`` with NO interpret argument
    anywhere in the call chain: the backend auto-selection
    (``pallas_kernels.auto_interpret``) picks the Pallas interpreter
    on the CPU backend, and the auto-selected path matches the INDEPENDENT numpy
    FV reference (test_sift_fv._np_fisher_vector) — parity against the
    spec translation, not merely against the jax program it fuses."""
    import jax

    from keystone_tpu.ops.images.pallas_kernels import auto_interpret
    from test_sift_fv import _np_fisher_vector

    assert auto_interpret(None) == (jax.default_backend() == "cpu")

    gmm = _random_model(d=8, k=32, seed=5)
    rng = np.random.default_rng(6)
    x = rng.standard_normal((8, 150)).astype(np.float32)
    got = np.asarray(FisherVectorFused(gmm).apply(x))
    want = _np_fisher_vector(
        np.asarray(gmm.means, np.float64),
        np.asarray(gmm.variances, np.float64),
        np.asarray(gmm.weights, np.float64),
        x.astype(np.float64),
        thresh=gmm.weight_threshold,
    )
    assert got.shape == want.shape == (8, 64)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_optimizable_choice_by_k():
    """One estimator, and the Fisher-vector node it returns follows the
    vocabulary: the Pallas kernel from k = 32 up, the XLA program below."""
    assert GMMFisherVectorEstimator(k=8)._choice() is FisherVector
    assert GMMFisherVectorEstimator(k=32)._choice() is FisherVectorFused


@pytest.mark.parametrize("k,node,path", [
    (2, FisherVector, "xla"), (32, FisherVectorFused, "pallas")])
def test_estimator_fits_counts_and_spans(k, node, path):
    """A fit returns the node of its vocabulary and leaves the ``fv`` and
    ``gmm`` families behind: spans ``fv.fit`` > ``gmm.init``, ``gmm.em``;
    counters of fits, EM rounds, the stop reason and the path."""
    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.observability.tracing import (
        disable_tracing, enable_tracing,
    )

    rng = np.random.default_rng(0)
    mats = jnp.asarray(rng.standard_normal((6, 8, 200)), jnp.float32)
    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        fv = GMMFisherVectorEstimator(k=k, seed=0).fit(
            Dataset.from_array(mats))
        names = [s.name for s in tr.recent()]
        counts = {
            (f.name, tuple(sorted(s.labels.items()))): s.value
            for f in get_global_registry().collect() for s in f.samples
            if s.suffix == ""
        }
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()
    assert type(fv) is node
    info = fv.gmm.fit_info
    for name in ("fv.fit", "gmm.init", "gmm.em"):
        assert names.count(name) == 1
    assert counts[("keystone_gmm_fits_total", ())] == 1
    assert counts[("keystone_gmm_em_iterations_total", ())] \
        == info["iterations"] >= 1
    assert counts[("keystone_gmm_stop_total",
                   (("reason", info["reason"]),))] == 1
    assert counts[("keystone_fv_path_total", (("path", path),))] == 1
    out = np.asarray(fv.apply_batch(Dataset.from_array(mats)).padded())
    assert out.shape == (6, 8, 2 * k) and np.all(np.isfinite(out))


def test_stats_kernel_matches_explicit_posteriors_at_published_widths():
    """``gmm_stats`` at d 80, k 256 in the interpreter, over a batch whose
    matrices end inside a tile, against float64 posteriors written out."""
    from keystone_tpu.ops.images.fv_pallas import gmm_stats

    rng = np.random.default_rng(7)
    b, d, m, k = 2, 80, 1500, 256
    x = rng.standard_normal((b, d, m)).astype(np.float32)
    mu = rng.standard_normal((d, k)).astype(np.float32)
    var = rng.uniform(0.5, 2.0, (d, k)).astype(np.float32)
    w = rng.dirichlet(np.ones(k)).astype(np.float32)
    s0, s1, s2, lse = map(np.asarray, gmm_stats(
        jnp.asarray(x), jnp.asarray(mu), jnp.asarray(var), jnp.asarray(w),
        1e-4))
    for i in range(b):
        xt = x[i].T.astype(np.float64)
        llh = (-0.5 * (xt * xt) @ (1 / var) + xt @ (mu / var)
               - 0.5 * np.sum(mu * mu / var, 0)
               - 0.5 * np.sum(np.log(2 * np.pi * var), 0) + np.log(w))
        top = llh.max(1, keepdims=True)
        e = np.exp(llh - top)
        q = e / e.sum(1, keepdims=True)
        q = np.where(q > 1e-4, q, 0.0)
        q /= q.sum(1, keepdims=True)
        for got, want in ((s0[i], q.sum(0)), (s1[i], xt.T @ q),
                          (s2[i], (xt * xt).T @ q)):
            assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
        want_lse = (top[:, 0] + np.log(e.sum(1))).sum()
        assert abs(lse[i] - want_lse) <= 1e-6 * abs(want_lse)
