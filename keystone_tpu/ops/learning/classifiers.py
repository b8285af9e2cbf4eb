"""Probabilistic classifiers: Naive Bayes, logistic regression, LDA.

Reference: nodes/learning/NaiveBayesModel.scala:21,62 (wraps MLlib
NaiveBayes; model emits log-posteriors π + θx),
LogisticRegressionModel.scala:19,42 (MLlib LBFGS LogisticGradient +
SquaredL2Updater, multinomial support),
LinearDiscriminantAnalysis.scala:17,39 (local multi-class LDA via
eig(S_w⁻¹ S_b)). All are small models: the sufficient statistics are
sharded-reduction matmuls; the solve/driver part is host/local.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg
from jax.experimental import sparse as jsparse

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.learning import sparse_buckets
from keystone_tpu.ops.learning.lbfgs import (
    count_lbfgs,
    run_lbfgs,
    run_lbfgs_device,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import LabelEstimator, Transformer


@dataclasses.dataclass(eq=False)
class NaiveBayesModel(Transformer):
    """x -> log-posterior scores π + θ·x (reference:
    NaiveBayesModel.scala:21 — argmax downstream picks the class)."""

    pi: Any  # (k,) log class priors
    theta: Any  # (k, d) log feature likelihoods

    def apply(self, x):
        if isinstance(x, jsparse.BCOO):
            return self.pi + x @ self.theta.T
        return self.pi + mm(x, self.theta.T)

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        if isinstance(x, jsparse.BCOO):
            scores = self.pi + jsparse.bcoo_dot_general(
                x, self.theta.T, dimension_numbers=(([1], [0]), ([], []))
            )
        else:
            scores = self.pi + mm(x, self.theta.T)
        return Dataset.from_array(scores * ds.mask()[:, None], n=ds.n)


@dataclasses.dataclass(eq=False)
class NaiveBayesEstimator(LabelEstimator):
    """Multinomial NB with Laplace smoothing (reference:
    NaiveBayesModel.scala:62 — MLlib NaiveBayes.train(lambda))."""

    num_classes: int
    lam: float = 1.0

    def fit(self, data: Dataset, labels: Dataset) -> NaiveBayesModel:
        # whole fit stays in the dispatch stream: pulling the labels to
        # the host would force the async pipeline to drain
        # int cast keeps the old np.eye semantics for float labels
        # (1.5 trains as 1); the range guard below then sees the same
        # values one_hot does
        y = jnp.asarray(labels.array()).reshape(-1).astype(jnp.int32)
        x = data.padded()
        onehot = jax.nn.one_hot(y, self.num_classes, dtype=jnp.float32)
        # one_hot maps out-of-range labels to a zero row, which would
        # silently drop those samples (np.eye indexing used to raise);
        # poison the model with NaN instead — loud, but still sync-free
        bad = jnp.any((y < 0) | (y >= self.num_classes))
        onehot = jnp.where(bad, jnp.nan, onehot)
        # pad rows of x are zero so the (k, d) count matmul is exact
        if isinstance(x, jsparse.BCOO):
            counts = jsparse.bcoo_dot_general(
                x, _pad_rows(onehot, x.shape[0]),
                dimension_numbers=(([0], [0]), ([], [])),
            ).T
        else:
            counts = mm(_pad_rows(onehot, x.shape[0]).T, x)
        class_counts = onehot.sum(axis=0)
        pi = jnp.log(class_counts + self.lam) - np.log(
            y.shape[0] + self.num_classes * self.lam
        )
        totals = jnp.sum(counts, axis=1, keepdims=True)
        theta = jnp.log(counts + self.lam) - jnp.log(
            totals + self.lam * counts.shape[1]
        )
        return NaiveBayesModel(pi, theta)


def _pad_rows(a: jnp.ndarray, n: int) -> jnp.ndarray:
    if a.shape[0] == n:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)]
    )


def _logistic_vg(W, x, onehot, mask, n, reg):
    """Softmax cross-entropy mean loss + L2 and its gradient — the
    traceable ``vg(W, *data)`` the fused device L-BFGS consumes (module
    level so the compiled optimizer is cached across fits)."""
    # HIGHEST for f32 (TPU DEFAULT truncates operands to bf16 —
    # block_ls._f32_mm); bf16 data keeps the native MXU path
    hp = (
        jax.lax.Precision.HIGHEST
        if not isinstance(x, jsparse.BCOO) and x.dtype == jnp.float32
        else None
    )
    if isinstance(x, jsparse.BCOO):
        logits = jsparse.bcoo_dot_general(
            x, W, dimension_numbers=(([1], [0]), ([], []))
        )
    else:
        logits = jnp.matmul(x, W, precision=hp)
    logz = jax.scipy.special.logsumexp(logits, axis=1)
    ll = jnp.sum((logz - jnp.sum(logits * onehot, axis=1)) * mask)
    p = jnp.exp(logits - logz[:, None]) * mask[:, None]
    if isinstance(x, jsparse.BCOO):
        g = jsparse.bcoo_dot_general(
            x, p - onehot, dimension_numbers=(([0], [0]), ([], []))
        )
    else:
        g = jnp.matmul(x.T, p - onehot, precision=hp)
    return ll / n + 0.5 * reg * jnp.sum(W * W), g / n + reg * W


_jit_logistic_vg = jax.jit(_logistic_vg)


# -- the grouped sparse path ------------------------------------------------
#
# BCOO products (``bcoo_dot_general``) lower to a gather and a scatter-
# add per non-zero and each direction; at a chip's share of a 65M-review
# corpus (358M non-zeros) the TPU compiler fails on them (its stack
# overflowed). The grouped path lays the BCOO's entries out twice, once
# a fit, in a program of its own (``SparseLogisticLayout.build``, scope
# ``logreg.layout``): grouped by row bucket for X·W and by column bucket
# for Xᵀ(P − Y), each sorted by its source within a bucket
# (``sparse_buckets``). Both directions of the gradient are then the
# bucket kernel (scope ``logreg.grad``): logits are gathered from W's
# columns and summed into a bucket of rows at a time, Xᵀ(P − Y) gathers
# the residuals and sums them into a bucket of columns — no scatter-add,
# so no update waits on another to the same hot column, and every sum
# is a float32 sum. Softmax logits enter only through their
# differences, so class 0's is held at 0 and k − 1 products a direction
# serve k classes (a row's residuals sum to 0: G_0 = −Σ_{c>0} G_c).


@jax.tree_util.register_dataclass
@dataclasses.dataclass(eq=False)
class SparseLogisticLayout:
    """The grouped gradient's two ``BucketLayout``s of a sparse (n, d)
    matrix: ``rows`` (destination row, source column: X·W) and ``cols``
    (destination column, source row: Xᵀ(P − Y))."""

    rows: Any
    cols: Any

    @staticmethod
    def build(x: jsparse.BCOO) -> "SparseLogisticLayout":
        """The layouts of an (n, d) BCOO, entries in any order, as one
        program."""
        with span("solver.logreg.layout", nnz=int(x.nse)):
            return _build_layout(x.data, x.indices, x.shape)


def _logreg_layout(data, indices, shape):
    n, d = shape
    with jax.named_scope("logreg.layout"):
        return SparseLogisticLayout(
            rows=sparse_buckets.bucket_layout(data, indices, 0, n, d),
            cols=sparse_buckets.bucket_layout(data, indices, 1, d, n))


_build_layout = jax.jit(_logreg_layout, static_argnums=(2,))


@partial(jax.jit, static_argnums=(2,))
def _sparse_scores(data, indices, n: int, W):
    """(n, k) = X·W of a BCOO's arrays by the bucket kernel."""
    with jax.named_scope("logreg.scores"):
        rows = sparse_buckets.bucket_layout(data, indices, 0, n, W.shape[0])
        return jnp.stack([sparse_buckets.bucket_product(rows, w, n)
                          for w in W.T], 1)


def _sparse_logistic_vg(W, layout, onehot, mask, n, reg):
    """``_logistic_vg`` on the grouped path: the same mean softmax
    cross-entropy + L2 and gradient, in the ``vg(W, *data)`` shape of
    the fused L-BFGS (module level: one compiled optimizer across
    fits)."""
    nr, d = onehot.shape[0], W.shape[0]
    with jax.named_scope("logreg.grad"):
        z = [sparse_buckets.bucket_product(layout.rows, w, nr)
             for w in (W[:, 1:] - W[:, :1]).T]
    logits = jnp.stack([jnp.zeros((nr,), jnp.float32)] + z, 1)
    top = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - top)
    s = jnp.sum(e, axis=1, keepdims=True)
    logz = jnp.log(s[:, 0]) + top[:, 0]
    ll = jnp.sum((logz - jnp.sum(logits * onehot, axis=1)) * mask)
    # P − Y to a few ulps of itself: the label's entry is minus the other
    # classes' share (p − 1 from a float32 p would keep an ulp of 1), and
    # e / Σe, not exp(z − log Σ), keeps log Σ's rounding out of p
    others = jnp.sum(e * (1.0 - onehot), axis=1, keepdims=True)
    resid = jnp.where(onehot > 0, -others, e) / s * mask[:, None]
    with jax.named_scope("logreg.grad"):
        g = jnp.stack([sparse_buckets.bucket_product(layout.cols, r, d)
                       for r in resid[:, 1:].T])  # (k − 1, d)
    grad = jnp.concatenate([-jnp.sum(g, 0, keepdims=True), g]).T
    return ll / n + 0.5 * reg * jnp.sum(W * W), grad / n + reg * W


_jit_sparse_logistic_vg = jax.jit(_sparse_logistic_vg)


def _device_onehot(labels, num_classes: int, rows: int):
    """(rows, k) float32 indicators of int labels, made on the device;
    an out-of-range label poisons them with NaN (``one_hot`` would give
    the row a zero indicator and drop it in silence)."""
    y = jnp.asarray(labels).reshape(-1).astype(jnp.int32)
    onehot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32)
    bad = jnp.any((y < 0) | (y >= num_classes))
    return _pad_rows(jnp.where(bad, jnp.nan, onehot), rows)


def _count_logreg_fit(path: str, nnz: int) -> None:
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_logreg_fits_total",
        "logistic regression fits started",
    ).inc()
    reg.counter(
        "keystone_solver_logreg_path_total",
        "logistic regression fits by the gradient's path",
        labelnames=("path",),
    ).inc((path,))
    reg.counter(
        "keystone_solver_sparse_nnz_total",
        "non-zeros of the sparse rows that grouped fits read",
    ).inc(by=nnz)


@dataclasses.dataclass(eq=False)
class LogisticRegressionModel(Transformer):
    """argmax-of-logits classifier (reference:
    LogisticRegressionModel.scala:19 — MLlib model.predict)."""

    W: Any  # (d, k)

    def apply(self, x):
        if isinstance(x, jsparse.BCOO):
            return jnp.argmax(x @ self.W, axis=-1)
        return jnp.argmax(mm(x, self.W), axis=-1)

    def scores_batch(self, ds: Dataset) -> Dataset:
        """(n, k) logits X·W; BCOO rows take the grouped path's bucket
        kernel."""
        x = ds.padded()
        if _grouped(x):
            scores = _sparse_scores(x.data, x.indices, x.shape[0], self.W)
        else:
            scores = mm(x, self.W)
        return Dataset.from_array(scores, n=ds.n)

    def apply_batch(self, ds: Dataset) -> Dataset:
        scores = self.scores_batch(ds).padded()
        return Dataset.from_array(jnp.argmax(scores, axis=-1), n=ds.n)


def _grouped(x) -> bool:
    """BCOO rows of one sparse (row, column) index a non-zero, of a
    shape whose tables the bucket kernel holds: the grouped path's
    input."""
    return isinstance(x, jsparse.BCOO) and x.n_batch == 0 \
        and x.n_dense == 0 and x.ndim == 2 and sparse_buckets.fits(*x.shape)


@dataclasses.dataclass(eq=False)
class LogisticRegressionEstimator(LabelEstimator):
    """Multinomial logistic regression by full-batch L-BFGS (reference:
    LogisticRegressionModel.scala:42 — MLlib LogisticRegressionWithLBFGS +
    SquaredL2Updater). The softmax cross-entropy gradient is one jitted
    program; the optimizer is the fused device L-BFGS by default
    (run_lbfgs_device — zero host syncs), or the f64 host driver. Sparse
    (BCOO) rows take the grouped path: layouts derived once a fit, then
    the gradient without BCOO products (the section above).

    Observability: host spans ``solver.logreg.layout`` (the layouts'
    program) and ``solver.logreg.dispatch`` (the fit's optimizer
    enqueued and its counts read); scopes ``logreg.layout`` and
    ``logreg.grad``; counters ``keystone_solver_logreg_fits_total``,
    ``keystone_solver_logreg_path_total{path=dense|grouped}``,
    ``keystone_solver_sparse_nnz_total`` and, for device fits,
    ``keystone_solver_lbfgs_iterations_total`` /
    ``_evaluations_total``."""

    num_classes: int
    num_iters: int = 20
    reg_param: float = 0.0
    convergence_tol: float = 1e-4
    driver: str = "device"

    def _problem(self, data: Dataset, labels: Dataset):
        """(vg, data tuple, d) of the objective the fit minimizes."""
        data = data.to_array_mode()
        x = data.padded()
        n, k = data.n, self.num_classes
        rows = x.shape[0]
        mask = data.mask()
        if _grouped(x):
            layout = SparseLogisticLayout.build(x)
            onehot = _device_onehot(labels.array(), k, rows)
            vg = _sparse_logistic_vg
            args = (layout, onehot)
        else:
            y = np.asarray(labels.array()).reshape(-1).astype(np.int64)
            if y.size and (y.min() < 0 or y.max() >= k):
                # np.eye(k)[y] would silently wrap negatives (e.g. -1/+1
                # binary labels) into valid classes and corrupt the fit
                raise ValueError(
                    f"labels must be class ids in [0, {k}); "
                    f"got range [{y.min()}, {y.max()}]"
                )
            onehot = jnp.asarray(_pad_rows(
                jnp.asarray(np.eye(k, dtype=np.float32)[y]), rows
            ))
            vg = _logistic_vg
            args = (x, onehot)
        return vg, args + (mask, jnp.float32(n),
                           jnp.float32(self.reg_param)), x.shape[1]

    def value_and_grad(self, data: Dataset, labels: Dataset, W):
        """(objective, gradient) at W by the path ``fit`` takes."""
        vg, args, _ = self._problem(data, labels)
        jit_vg = (_jit_sparse_logistic_vg if vg is _sparse_logistic_vg
                  else _jit_logistic_vg)
        return jit_vg(jnp.asarray(W, jnp.float32), *args)

    def fit(self, data: Dataset, labels: Dataset) -> LogisticRegressionModel:
        if self.driver not in ("device", "host"):
            raise ValueError(f"driver must be 'device' or 'host', got {self.driver!r}")
        vg, args, d = self._problem(data, labels)
        k = self.num_classes
        grouped = vg is _sparse_logistic_vg
        _count_logreg_fit(
            "grouped" if grouped else "dense",
            int(data.to_array_mode().padded().nse) if grouped else 0)

        if self.driver == "device":
            with span("solver.logreg.dispatch"):
                W, iters, evals = run_lbfgs_device(
                    vg,  # module-level: jit cache shared across fits
                    jnp.zeros((d, k), jnp.float32),
                    self.num_iters, convergence_tol=self.convergence_tol,
                    data=args,
                )
                count_lbfgs(iters, evals)
            if grouped:  # an out-of-range label poisoned the indicators
                W = jnp.where(jnp.any(jnp.isnan(args[1])), jnp.nan, W)
            return LogisticRegressionModel(W)

        jit_vg = _jit_sparse_logistic_vg if grouped else _jit_logistic_vg

        def host_vg(w_flat):
            W = jnp.asarray(w_flat.reshape(d, k).astype(np.float32))
            f, g = jit_vg(W, *args)
            return float(f), np.asarray(g, np.float64).ravel()

        w = run_lbfgs(
            host_vg, np.zeros((d, k)), self.num_iters,
            convergence_tol=self.convergence_tol,
        )
        return LogisticRegressionModel(
            jnp.asarray(w.reshape(d, k).astype(np.float32))
        )


@dataclasses.dataclass(eq=False)
class LinearDiscriminantAnalysis(LabelEstimator):
    """Multi-class LDA: project onto the top eigenvectors of S_w⁻¹ S_b
    (reference: LinearDiscriminantAnalysis.scala:17,39 — local eig)."""

    num_dimensions: int

    def fit(self, data: Dataset, labels: Dataset):
        from keystone_tpu.ops.learning.linear import LinearMapper

        X = np.asarray(data.array(), np.float64)
        y = np.asarray(labels.array()).reshape(-1).astype(np.int64)
        classes = np.unique(y)
        d = X.shape[1]
        overall_mean = X.mean(axis=0)
        Sw = np.zeros((d, d))
        Sb = np.zeros((d, d))
        for c in classes:
            Xc = X[y == c]
            mu_c = Xc.mean(axis=0)
            centered = Xc - mu_c
            Sw += centered.T @ centered
            diff = (mu_c - overall_mean)[:, None]
            Sb += Xc.shape[0] * (diff @ diff.T)
        evals, evecs = scipy.linalg.eig(Sb, Sw)
        order = np.argsort(-evals.real)
        W = evecs[:, order[: self.num_dimensions]].real
        return LinearMapper(jnp.asarray(W, jnp.float32))
