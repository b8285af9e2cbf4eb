"""Fisher vector encoding from GMM posteriors.

Reference: nodes/images/FisherVector.scala:21-94 (the Sanchez et al. FV
survey formulation) and nodes/images/external/FisherVector.scala:17
(enceval JNI variant — on TPU the "native" path is the Pallas kernel of
fv_pallas.py, which GMMFisherVectorEstimator picks from k >= 32).

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
from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import Estimator, Transformer


@partial(jax.jit, static_argnums=(0,))
def _fisher_vector(fv_self, x):
    return _fisher_of(fv_self.gmm, x)


def _fisher_of(gmm, x):
    """x: (d, m) descriptors. Direct transliteration of the Sanchez
    formulas (FisherVector.scala:33-52)."""
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

    def rowwise(self):
        g = self.gmm
        return (_FisherRows(False, float(g.weight_threshold)),
                (g.means, g.variances, g.weights))

    def apply(self, x):
        return _fisher_vector(self, jnp.asarray(x, jnp.float32))

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            out = jax.vmap(lambda m: _fisher_vector(self, m))(
                ds.padded().astype(jnp.float32)
            )
            return Dataset.from_array(out, n=ds.n)
        return ds.map(self.apply)


@dataclasses.dataclass(frozen=True)
class _FisherRows:
    """A Fisher-vector node's rows-in, rows-out function (see
    ``Transformer.rowwise``): ``fused`` takes the statistics from the
    Pallas kernel, else from the plain XLA program; the GMM's arrays are
    arguments, so two fits share the compiled programs."""

    fused: bool
    weight_threshold: float
    groups_only = True

    def __call__(self, arrays, x):
        gmm = GaussianMixtureModel(*arrays, self.weight_threshold)
        if not self.fused:
            return jax.vmap(partial(_fisher_of, gmm))(x.astype(jnp.float32))
        from keystone_tpu.ops.images.fv_pallas import gmm_stats

        with jax.named_scope("fv.stats"):
            s0, s1, s2, _ = gmm_stats(
                x, gmm.means, gmm.variances, gmm.weights,
                self.weight_threshold,
            )
        inv_m = 1.0 / x.shape[2]
        return jax.vmap(partial(_fv_from_stats, gmm))(
            s0 * inv_m, s1 * inv_m, s2 * inv_m
        )


@dataclasses.dataclass(eq=False)
class FisherVectorFused(Transformer):
    """FV via the fused Pallas statistics kernel (the TPU equivalent of
    the reference's enceval-native path, external/FisherVector.scala:17 →
    EncEval.cxx:19): posterior computation and the statistics matmuls run
    in one kernel over a whole batch of descriptor matrices, never writing
    the (m, k) posterior matrix to HBM — the win grows with k, hence the
    k >= 32 physical choice in GMMFisherVectorEstimator."""

    gmm: GaussianMixtureModel

    def rowwise(self):
        g = self.gmm
        return (_FisherRows(True, float(g.weight_threshold)),
                (g.means, g.variances, g.weights))

    def apply(self, x):
        return self.apply_batch(
            Dataset.from_array(jnp.asarray(x, jnp.float32)[None])
        ).padded()[0]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            fn, arrays = self.rowwise()
            return Dataset.from_array(fn(arrays, ds.padded()), n=ds.n)
        return ds.map(self.apply)


FUSED_MIN_K = 32  # FisherVector.scala:84-94: the native path from here up


def _columns_of(data: Dataset):
    """Flatten (d, m) descriptor matrices into one (N, d) row matrix for
    GMM training (reference: flatMap(matrixToColArray)), on the device."""
    if data.is_array:
        x = data.array()  # (n, d, m)
        rows = jnp.transpose(x, (0, 2, 1)).reshape(-1, x.shape[1])
    else:
        rows = jnp.concatenate([jnp.asarray(m).T for m in data.items()])
    return Dataset.from_array(rows)


@dataclasses.dataclass(eq=False)
class GMMFisherVectorEstimator(Estimator):
    """GMM fit, then the Fisher-vector node for its vocabulary
    (reference: FisherVector.scala:65 and external/FisherVector.scala:49,
    between which FisherVector.scala:84-94 picks the native enceval
    implementation when k >= 32): large k takes the fused Pallas kernel
    (posteriors stay in VMEM), small k the plain XLA program (kernel
    launch overhead dominates). Span ``fv.fit``; counter
    ``keystone_fv_path_total{path}``."""

    k: int
    seed: int = 0

    def _choice(self) -> type:
        return FisherVectorFused if self.k >= FUSED_MIN_K else FisherVector

    def fit(self, data: Dataset) -> Transformer:
        with span("fv.fit", k=self.k):
            gmm = GaussianMixtureModelEstimator(self.k, seed=self.seed).fit(
                _columns_of(data)
            )
        node = self._choice()
        get_global_registry().counter(
            "keystone_fv_path_total",
            "Fisher-vector nodes fitted, by the program that computes "
            "their statistics",
            labelnames=("path",),
        ).inc(("pallas" if node is FisherVectorFused else "xla",))
        return node(gmm)

    def fit_datasets(self, datasets):
        return self.fit(datasets[0])
