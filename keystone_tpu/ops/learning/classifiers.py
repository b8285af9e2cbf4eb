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
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg
from jax.experimental import sparse as jsparse

from keystone_tpu.ops.learning.lbfgs import run_lbfgs, run_lbfgs_device
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


@dataclasses.dataclass(eq=False)
class LogisticRegressionModel(Transformer):
    """argmax-of-logits classifier (reference:
    LogisticRegressionModel.scala:19 — MLlib model.predict)."""

    W: Any  # (d, k)

    def apply(self, x):
        if isinstance(x, jsparse.BCOO):
            return jnp.argmax(x @ self.W, axis=-1)
        return jnp.argmax(mm(x, self.W), axis=-1)

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        if isinstance(x, jsparse.BCOO):
            scores = jsparse.bcoo_dot_general(
                x, self.W, dimension_numbers=(([1], [0]), ([], []))
            )
        else:
            scores = mm(x, self.W)
        return Dataset.from_array(jnp.argmax(scores, axis=-1), n=ds.n)


@dataclasses.dataclass(eq=False)
class LogisticRegressionEstimator(LabelEstimator):
    """Multinomial logistic regression by full-batch L-BFGS (reference:
    LogisticRegressionModel.scala:42 — MLlib LogisticRegressionWithLBFGS +
    SquaredL2Updater). Softmax cross-entropy gradient is one jitted sharded
    program; the optimizer is the fused device L-BFGS by default
    (run_lbfgs_device — zero host syncs), or the f64 host driver."""

    num_classes: int
    num_iters: int = 20
    reg_param: float = 0.0
    convergence_tol: float = 1e-4
    driver: str = "device"

    def fit(self, data: Dataset, labels: Dataset) -> LogisticRegressionModel:
        if self.driver not in ("device", "host"):
            raise ValueError(f"driver must be 'device' or 'host', got {self.driver!r}")
        y = np.asarray(labels.array()).reshape(-1).astype(np.int64)
        if y.size and (y.min() < 0 or y.max() >= self.num_classes):
            # np.eye(k)[y] would silently wrap negatives (e.g. -1/+1
            # binary labels) into valid classes and corrupt the fit
            raise ValueError(
                f"labels must be class ids in [0, {self.num_classes}); "
                f"got range [{y.min()}, {y.max()}]"
            )
        data = data.to_array_mode()
        x = data.padded()
        n = data.n
        d = x.shape[1]
        k = self.num_classes
        onehot = jnp.asarray(_pad_rows(
            jnp.asarray(np.eye(k, dtype=np.float32)[y]), x.shape[0]
        ))
        mask = data.mask()

        if self.driver == "device":
            W = run_lbfgs_device(
                _logistic_vg,  # module-level: jit cache shared across fits
                jnp.zeros((d, k), jnp.float32),
                self.num_iters, convergence_tol=self.convergence_tol,
                data=(x, onehot, mask, jnp.float32(n),
                      jnp.float32(self.reg_param)),
            )
            return LogisticRegressionModel(W)

        def vg(w_flat):
            W = jnp.asarray(w_flat.reshape(d, k).astype(np.float32))
            f, g = _jit_logistic_vg(
                W, x, onehot, mask, jnp.float32(n),
                jnp.float32(self.reg_param),
            )
            return float(f), np.asarray(g, np.float64).ravel()

        w = run_lbfgs(
            vg, np.zeros((d, k)), self.num_iters,
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
