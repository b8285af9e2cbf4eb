"""PCA family: local SVD, distributed TSQR, randomized sketch, and the
cost-model-selected column variant.

Reference: nodes/learning/PCA.scala (PCATransformer:19,
BatchPCATransformer:38, PCAEstimator:163-225 with MATLAB sign convention
:227-248, ColumnPCAEstimator:51-156), DistributedPCA.scala:20 (mlmatrix
TSQR), ApproximatePCA.scala:22 (Halko-Martinsson-Tropp randomized range
finder).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.learning.cost import CostModel
from keystone_tpu.parallel import linalg as plinalg
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import Estimator, Transformer


def enforce_matlab_pca_sign_convention(pca: jnp.ndarray) -> jnp.ndarray:
    """Largest-|element| entry of each column gets a positive sign
    (reference: PCA.scala:227-248)."""
    col_maxs = jnp.max(pca, axis=0)
    abs_col_maxs = jnp.max(jnp.abs(pca), axis=0)
    signs = jnp.where(col_maxs == abs_col_maxs, 1.0, -1.0)
    return pca * signs[None, :]


@dataclasses.dataclass(eq=False)
class PCATransformer(Transformer):
    """x -> pca_matᵀ x for vectors (reference: PCA.scala:19)."""

    pca_mat: Any  # (d, dims)

    def apply(self, x):
        return mm(x, self.pca_mat)

    def apply_batch(self, ds: Dataset) -> Dataset:
        return Dataset.from_array(mm(ds.padded(), self.pca_mat), n=ds.n)


@dataclasses.dataclass(eq=False)
class BatchPCATransformer(Transformer):
    """(d, m) descriptor matrix -> (dims, m) (reference: PCA.scala:38 —
    pcaMat.t * in)."""

    pca_mat: Any  # (d, dims)
    vmap_batch = True

    def apply(self, m):
        return mm(self.pca_mat.T, m)

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(
                _project_columns(self.pca_mat, ds.padded()), n=ds.n)
        return ds.map(self.apply)

    def rowwise(self):
        return _project_columns, self.pca_mat


def _project_columns(pca_mat, x):
    """BatchPCATransformer's rows-in, rows-out function: (n, d, m)
    descriptor matrices onto the (d, dims) basis."""
    with jax.named_scope("pca.project"):
        return jnp.einsum("dk,ndm->nkm", pca_mat, x)


_project_columns.groups_only = True


@jax.jit
def _centered_gram(data_mat):
    """(X - mean)ᵀ (X - mean) of the rows, float32 at HIGHEST: the one
    pass over the sample a PCA fit makes."""
    with jax.named_scope("pca.cov"):
        centered = data_mat - jnp.mean(data_mat, axis=0)
        return jnp.matmul(
            centered.T, centered, precision=jax.lax.Precision.HIGHEST
        )


def _compute_pca(data_mat: jnp.ndarray, dims: int) -> jnp.ndarray:
    """Center, principal directions, sign convention, truncate
    (reference: PCA.scala:180-203 computePCA, which takes the right
    singular vectors of the centered sample from LAPACK on the driver).
    Here the sample stays on the device: its centered Gram is one device
    program, and the driver's part is the float64 ``eigh`` of that (d, d)
    matrix, whose eigenvectors by falling eigenvalue are those singular
    vectors. Span ``pca.fit``, counter ``keystone_pca_fits_total``."""
    with span("pca.fit", n=data_mat.shape[0], d=data_mat.shape[1]):
        gram = np.asarray(_centered_gram(jnp.asarray(data_mat)), np.float64)
        _, vecs = np.linalg.eigh(gram)
        pca = enforce_matlab_pca_sign_convention(
            jnp.asarray(vecs[:, ::-1], jnp.float32))
    get_global_registry().counter(
        "keystone_pca_fits_total", "PCA fits from a sample's centered Gram"
    ).inc()
    return pca[:, :dims]


@dataclasses.dataclass(eq=False)
class PCAEstimator(Estimator, CostModel):
    """Local PCA: materialize the sample, one SVD (reference:
    PCA.scala:163-225 — collect + LAPACK sgesvd; here the SVD runs on
    device)."""

    dims: int

    def fit(self, data: Dataset) -> PCATransformer:
        x = data.array()
        return PCATransformer(_compute_pca(jnp.asarray(x), self.dims))

    def cost(self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
             network_weight):
        # reference: PCA.scala:205-225 — collect everything to one place
        flops = float(n) * d * d
        bytes_scanned = float(n) * d
        network = float(n) * d
        return (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


@dataclasses.dataclass(eq=False)
class DistributedPCAEstimator(Estimator, CostModel):
    """Distributed PCA via TSQR: R of the sharded centered matrix, then a
    local SVD of R (reference: DistributedPCA.scala:20,34-57 — mlmatrix
    `new TSQR().qrR` + driver-side SVD)."""

    dims: int

    def fit(self, data: Dataset) -> PCATransformer:
        ds = data.to_array_mode()
        x = ds.padded()
        mask = ds.mask()
        mu = jnp.sum(x * mask[:, None], axis=0) / ds.n
        centered = (x - mu) * mask[:, None]
        r = plinalg.tsqr_r(centered)
        _, _, vt = jnp.linalg.svd(r, full_matrices=False)
        pca = enforce_matlab_pca_sign_convention(vt.T)
        return PCATransformer(pca[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
             network_weight):
        # reference: DistributedPCA.scala:59-73 — n d²/m + d³ log m
        flops = float(n) * d * d / num_machines + float(d) ** 3 * max(
            np.log2(num_machines), 1.0
        )
        bytes_scanned = float(n) * d / num_machines
        network = float(d) * d * max(np.log2(num_machines), 1.0)
        return (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


@dataclasses.dataclass(eq=False)
class ApproximatePCAEstimator(Estimator, CostModel):
    """Randomized sketch PCA (Halko-Martinsson-Tropp algs 4.4 + 5.1;
    reference: ApproximatePCA.scala:22,37,67): range finder with ``q``
    power iterations on an (n, dims+p) sketch, then SVD of the small
    projected matrix."""

    dims: int
    p: int = 10  # oversampling
    q: int = 2  # power iterations
    seed: int = 0

    def fit(self, data: Dataset) -> PCATransformer:
        ds = data.to_array_mode()
        x = ds.padded()
        mask = ds.mask()
        mu = jnp.sum(x * mask[:, None], axis=0) / ds.n
        A = (x - mu) * mask[:, None]
        d = A.shape[1]
        l = min(self.dims + self.p, d)
        key = jax.random.PRNGKey(self.seed)
        omega = jax.random.normal(key, (d, l), jnp.float32)
        Y = mm(A, omega)  # (and B below): policy precision — B feeds the
        # SVD directly, so truncation there lands in the PCA directions
        Q, _ = jnp.linalg.qr(Y)
        for _ in range(self.q):  # power iterations for spectral decay
            Z, _ = jnp.linalg.qr(mm(A.T, Q))
            Q, _ = jnp.linalg.qr(mm(A, Z))
        B = mm(Q.T, A)  # (l, d)
        _, _, vt = jnp.linalg.svd(B, full_matrices=False)
        pca = enforce_matlab_pca_sign_convention(vt.T)
        return PCATransformer(pca[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
             network_weight):
        l = self.dims + self.p
        flops = float(n) * d * l * (1 + self.q) / num_machines
        bytes_scanned = float(n) * d / num_machines
        network = float(d) * l
        return (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


def _columns_dataset(data: Dataset) -> Dataset:
    """Flatten a dataset of (d, m) descriptor matrices into one (N, d)
    array of descriptor columns (reference: LocalColumnPCAEstimator —
    flatMap(matrixToColArray))."""
    if data.is_array:
        x = data.array()  # (n, d, m), on the device and staying there
        return Dataset.from_array(
            jnp.transpose(x, (0, 2, 1)).reshape(-1, x.shape[1]))
    return Dataset.from_array(
        jnp.concatenate([jnp.asarray(m).T for m in data.items()]))


@dataclasses.dataclass(eq=False)
class LocalColumnPCAEstimator(Estimator, CostModel):
    """Column-wise local PCA over matrix items (reference:
    PCA.scala:51-70)."""

    dims: int

    def fit(self, data: Dataset) -> BatchPCATransformer:
        t = PCAEstimator(self.dims).fit(_columns_dataset(data))
        return BatchPCATransformer(t.pca_mat)

    def cost(self, *a, **kw):
        return PCAEstimator(self.dims).cost(*a, **kw)


@dataclasses.dataclass(eq=False)
class DistributedColumnPCAEstimator(Estimator, CostModel):
    """Column-wise distributed PCA (reference: PCA.scala:81-102)."""

    dims: int

    def fit(self, data: Dataset) -> BatchPCATransformer:
        t = DistributedPCAEstimator(self.dims).fit(
            _columns_dataset(data).shard()
        )
        return BatchPCATransformer(t.pca_mat)

    def cost(self, *a, **kw):
        return DistributedPCAEstimator(self.dims).cost(*a, **kw)


@dataclasses.dataclass(eq=False)
class ColumnPCAEstimator(Estimator):
    """Cost-model choice between local and distributed column PCA
    (reference: PCA.scala:118-156 — OptimizableEstimator), made at the
    fit from the sample it is handed: its size is then known, and no pass
    over a sample of the images is spent on asking (the reference's
    optimizer rule runs the featurizer on a sample to choose). One machine
    has nothing to distribute over and fits locally."""

    dims: int
    num_machines: Optional[int] = None

    def _options(self):
        return [
            LocalColumnPCAEstimator(self.dims),
            DistributedColumnPCAEstimator(self.dims),
        ]

    def fit(self, data: Dataset):
        return self.optimize([data], data.n).fit(data)

    def fit_datasets(self, datasets):
        return self.fit(datasets[0])

    def optimize(self, samples, n_total: int):
        sample: Dataset = samples[0]
        first = np.asarray(sample.first())
        d = first.shape[0]
        cols_per_item = first.shape[1] if first.ndim > 1 else 1
        n = max(n_total, sample.n) * cols_per_item
        machines = self.num_machines or max(
            len(jax.devices()), 1
        )
        if machines == 1:
            return LocalColumnPCAEstimator(self.dims)
        from keystone_tpu.ops.learning.cost import (
            TPU_CPU_WEIGHT,
            TPU_MEM_WEIGHT,
            TPU_NETWORK_WEIGHT,
        )

        return min(
            self._options(),
            key=lambda o: o.cost(
                n, d, self.dims, 1.0, machines,
                TPU_CPU_WEIGHT, TPU_MEM_WEIGHT, TPU_NETWORK_WEIGHT,
            ),
        )
