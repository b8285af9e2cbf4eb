"""Fisher vector encoding from GMM posteriors.

Reference: nodes/images/FisherVector.scala:21-94 (the Sanchez et al. FV
survey formulation) and nodes/images/external/FisherVector.scala:17
(enceval JNI variant — on TPU the "native" path is the same fused XLA
program, so GMMFisherVectorEstimator's k>=32 native switch collapses to
one implementation).

Input per example: a (d, m) descriptor matrix (d descriptor dims, m
descriptors, the SIFT/LCS output convention); output: the (d, 2k) FV.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from keystone_tpu.ops.learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import Estimator, Transformer
from keystone_tpu.workflow.node_optimization import Optimizable


@partial(jax.jit, static_argnums=(0,))
def _fisher_vector(fv_self, x):
    """x: (d, m) descriptors. Direct transliteration of the Sanchez
    formulas (FisherVector.scala:33-52)."""
    gmm = fv_self.gmm
    m = x.shape[1]
    with jax.named_scope("fv.posteriors"):
        q = gmm._posteriors(x.T)  # (m, k)
    with jax.named_scope("fv.stats"):
        s0 = jnp.mean(q, axis=0)  # (k,)
        s1 = mm(x, q) / m  # (d, k)
        s2 = mm(x * x, q) / m  # (d, k)
    return _fv_from_stats(gmm, s0, s1, s2)


def _fv_from_stats(gmm, s0, s1, s2):
    """Sanchez FV from the (already /m) statistics
    (FisherVector.scala:42-52)."""
    means, variances = gmm.means, gmm.variances  # (d, k)
    weights = gmm.weights  # (k,)
    with jax.named_scope("fv.normalize"):
        fv1 = (s1 - means * s0[None, :]) / (
            jnp.sqrt(variances) * jnp.sqrt(weights)[None, :]
        )
        fv2 = (
            s2
            - 2.0 * means * s1
            + (means * means - variances) * s0[None, :]
        ) / (variances * jnp.sqrt(2.0 * weights)[None, :])
        return jnp.concatenate([fv1, fv2], axis=1)  # (d, 2k)


@dataclasses.dataclass(eq=False)
class FisherVector(Transformer):
    gmm: GaussianMixtureModel

    def apply(self, x):
        return _fisher_vector(self, jnp.asarray(x, jnp.float32))

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            out = jax.vmap(lambda m: _fisher_vector(self, m))(
                ds.padded().astype(jnp.float32)
            )
            return Dataset.from_array(out, n=ds.n)
        return ds.map(self.apply)


@dataclasses.dataclass(eq=False)
class FisherVectorFused(Transformer):
    """FV via the fused Pallas statistics kernel (the TPU equivalent of
    the reference's enceval-native path, external/FisherVector.scala:17 →
    EncEval.cxx:19): posterior computation and the three statistics
    matmuls run in one kernel, never writing the (m, k) posterior matrix
    to HBM — the win grows with k, hence the k >= 32 physical choice in
    GMMFisherVectorEstimator."""

    gmm: GaussianMixtureModel

    def apply(self, x):
        from keystone_tpu.ops.images.fv_pallas import (
            fisher_vector_stats_pallas,
        )

        g = self.gmm
        with jax.named_scope("fv.stats"):
            s0, s1, s2 = fisher_vector_stats_pallas(
                jnp.asarray(x, jnp.float32), g.means, g.variances,
                g.weights, g.weight_threshold,
            )
        return _fv_from_stats(g, s0, s1, s2)

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            out = jax.vmap(self.apply)(ds.padded().astype(jnp.float32))
            return Dataset.from_array(out, n=ds.n)
        return ds.map(self.apply)


def _columns_of(data: Dataset):
    """Flatten (d, m) descriptor matrices into one (N, d) row matrix for
    GMM training (reference: flatMap(matrixToColArray))."""
    import numpy as np

    cols = [np.asarray(m).T for m in data.items()]
    return Dataset.from_array(jnp.asarray(np.concatenate(cols, axis=0)))


@dataclasses.dataclass(eq=False)
class ScalaGMMFisherVectorEstimator(Estimator):
    """GMM-fit + unfused FisherVector (reference: FisherVector.scala:65
    — the Scala implementation parallel)."""

    k: int
    seed: int = 0

    def fit(self, data: Dataset) -> FisherVector:
        gmm = GaussianMixtureModelEstimator(self.k, seed=self.seed).fit(
            _columns_of(data)
        )
        return FisherVector(gmm)


@dataclasses.dataclass(eq=False)
class EncEvalGMMFisherVectorEstimator(Estimator):
    """GMM-fit + fused-kernel FisherVector (reference:
    external/FisherVector.scala:49 — the enceval-native parallel; here
    the native path is the Pallas kernel in fv_pallas.py)."""

    k: int
    seed: int = 0

    def fit(self, data: Dataset) -> FisherVectorFused:
        gmm = GaussianMixtureModelEstimator(self.k, seed=self.seed).fit(
            _columns_of(data)
        )
        return FisherVectorFused(gmm)


@dataclasses.dataclass(eq=False)
class GMMFisherVectorEstimator(Estimator, Optimizable):
    """Optimizable physical choice (reference: FisherVector.scala:84-94
    picks the native enceval implementation when k >= 32): large k favors
    the fused Pallas kernel (posteriors stay in VMEM); small k favors the
    plain XLA program (kernel launch overhead dominates)."""

    k: int
    seed: int = 0

    def _choice(self) -> Estimator:
        if self.k >= 32:
            return EncEvalGMMFisherVectorEstimator(self.k, self.seed)
        return ScalaGMMFisherVectorEstimator(self.k, self.seed)

    def fit(self, data: Dataset) -> Transformer:
        return self._choice().fit(data)

    def fit_datasets(self, datasets):
        return self.fit(datasets[0])

    def optimize(self, samples, n_total: int):
        return self._choice()
