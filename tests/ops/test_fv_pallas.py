"""Fused (Pallas) vs unfused Fisher-vector path equivalence, and the
k-threshold physical choice (reference: FisherVector.scala:84-94,
EncEvalSuite fixture constant)."""

import numpy as np
import pytest
import jax.numpy as jnp

from keystone_tpu.ops.images.fisher_vector import (
    EncEvalGMMFisherVectorEstimator,
    FisherVector,
    FisherVectorFused,
    GMMFisherVectorEstimator,
    ScalaGMMFisherVectorEstimator,
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
    small = GMMFisherVectorEstimator(k=8)
    large = GMMFisherVectorEstimator(k=32)
    assert isinstance(small._choice(), ScalaGMMFisherVectorEstimator)
    assert isinstance(large._choice(), EncEvalGMMFisherVectorEstimator)
    assert isinstance(
        small.optimize(None, 0), ScalaGMMFisherVectorEstimator
    )
    assert isinstance(
        large.optimize(None, 0), EncEvalGMMFisherVectorEstimator
    )
