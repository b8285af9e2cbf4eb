"""Diagonal-covariance Gaussian mixture model (soft assignment + local EM).

Reference: nodes/learning/GaussianMixtureModel.scala (batch Mahalanobis +
shifted-softmax posterior + aggressive thresholding, :19-97, csv load
:97-110) and GaussianMixtureModelEstimator.scala:25-203 (k-means++ or
random init, variance flooring, incremental log-sum-exp cost, min-cluster
guard).

One estimator. The sample stays on the device from the moment it is
handed over: the k-means++ draw is one device program fed by the host's
uniforms (``_gmm_init``), the EM one ``lax.while_loop`` with the
convergence test, the cluster floor and the variance floors in the loop
(``_gmm_em``), and neither writes a row's posteriors out: an E-step is a
blocked pass of ``fv_pallas.gmm_stats`` that hands back Σq, Σxq, Σx²q and
the cost. The reference's scala/enceval pair of EMs (and the host-stepped
and fused pair that stood for it here) computed the same thing twice.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Estimator, Transformer

KMEANS_PLUS_PLUS_INITIALIZATION = "kmeans++"
RANDOM_INITIALIZATION = "random"


@dataclasses.dataclass(eq=False)
class GaussianMixtureModel(Transformer):
    """Thresholded posterior assignments. ``means``/``variances`` are
    (dims, k) — each column one cluster, matching the reference ctor so
    csv fixtures load identically."""

    means: Any  # (d, k)
    variances: Any  # (d, k)
    weights: Any  # (k,)
    weight_threshold: float = 1e-4

    @property
    def k(self) -> int:
        return self.means.shape[1]

    @property
    def dim(self) -> int:
        return self.means.shape[0]

    def _posteriors(self, X):
        # (d, k) operands consumed directly — transposing captured
        # constants inside a fused jit program miscompiles on some TPU
        # backends (observed: posteriors computed against wrong means)
        llh = _log_likelihoods_dk(
            X, self.means, self.variances, self.weights
        )
        # shifted softmax (peak at 0) + aggressive thresholding
        llh = llh - jnp.max(llh, axis=1, keepdims=True)
        q = jnp.exp(llh)
        q = q / jnp.sum(q, axis=1, keepdims=True)
        q = jnp.where(q > self.weight_threshold, q, 0.0)
        return q / jnp.sum(q, axis=1, keepdims=True)

    def apply(self, x):
        return self._posteriors(x[None, :])[0]

    def apply_batch(self, ds: Dataset) -> Dataset:
        q = self._posteriors(ds.padded())
        return Dataset.from_array(q * ds.mask()[:, None], n=ds.n)

    @staticmethod
    def load(mean_file: str, vars_file: str, weights_file: str,
             delimiter: str = ",") -> "GaussianMixtureModel":
        """CSV load (reference: GaussianMixtureModel.scala:97-110)."""
        means = np.loadtxt(mean_file, delimiter=delimiter, ndmin=2)
        variances = np.loadtxt(vars_file, delimiter=delimiter, ndmin=2)
        weights = np.loadtxt(weights_file, delimiter=delimiter).reshape(-1)
        return GaussianMixtureModel(
            jnp.asarray(means, jnp.float32),
            jnp.asarray(variances, jnp.float32),
            jnp.asarray(weights, jnp.float32),
        )


@jax.jit
def _log_likelihoods_dk(X, mu_dk, var_dk, weights):
    """(n, k) log p(x, cluster): −½‖x−μ‖²_Λ − ½Σlog var + log w + const
    (reference: GaussianMixtureModel.scala:47-66). ``mu_dk``/``var_dk``
    are (d, k) — the model's native layout; no transposes occur in the
    program (see _posteriors for why)."""
    d = X.shape[1]
    xsq = X * X
    # HIGHEST precision: TPU's default bf16 matmul passes lose ~3 decimal
    # digits here, which the softmax amplifies into materially different
    # posteriors (the reference computes these in f64 on CPU)
    hp = jax.lax.Precision.HIGHEST
    sq_mahl = (
        jnp.matmul(xsq, 0.5 / var_dk, precision=hp)
        - jnp.matmul(X, mu_dk / var_dk, precision=hp)
        + 0.5 * jnp.sum(mu_dk * mu_dk / var_dk, axis=0)[None, :]
    )
    return (
        -0.5 * d * jnp.log(2 * jnp.pi)
        - 0.5 * jnp.sum(jnp.log(var_dk), axis=0)[None, :]
        + jnp.log(weights)[None, :]
        - sq_mahl
    )


STOP_REASONS = ("tolerance", "max_iter", "cluster_floor")


def _stats(xt, mu, var, weights, threshold, hard=False):
    """Σq (k,), Σxq (k, d), Σx²q (k, d) and Σ log-likelihood over the
    columns of ``xt`` (d, n), by the blocked kernel; ``mu`` / ``var`` are
    (k, d)."""
    from keystone_tpu.ops.images.fv_pallas import gmm_stats

    s0, s1, s2, lse = gmm_stats(
        xt[None], mu.T, var.T, weights, threshold, hard=hard
    )
    return s0[0], s1[0].T, s2[0].T, lse[0]


def _variance_floor(xt, var_floors):
    """The larger of ``var_floors[0]`` of each dimension's variance over
    the sample and ``var_floors[1]`` (the reference's small and absolute
    variance thresholds)."""
    mean = jnp.mean(xt, axis=1)
    var_global = jnp.mean(xt * xt, axis=1) - mean * mean
    return jnp.maximum(var_floors[0] * var_global, var_floors[1])


@partial(jax.jit, static_argnames=("k",))
def _gmm_init(xt, first, uniforms, spare, var_floors, *, k: int):
    """k-means++ seeds, one Lloyd round and the moments of its clusters,
    all on the device (GaussianMixtureModelEstimator.scala:60-90 through
    KMeansPlusPlus.scala:83-140): ``first`` is the first seed's column,
    ``uniforms`` (k - 1,) the host generator's draws, one a seed, each
    placed on the running D² distribution by a cumulative sum; ``spare``
    the columns taken where every point already is a seed. Returns (mu,
    var, weights, var_lb, seeds)."""
    d, n = xt.shape
    hp = jax.lax.Precision.HIGHEST
    with jax.named_scope("gmm.init"):
        half_sq = 0.5 * jnp.sum(xt * xt, axis=0)

        def seed(j, state):
            seeds, dist = state
            c = jax.lax.dynamic_slice_in_dim(xt, seeds[j], 1, axis=1)[:, 0]
            new = half_sq - jnp.matmul(c, xt, precision=hp) + 0.5 * (c @ c)
            dist = jnp.minimum(dist, new)
            cdf = jnp.cumsum(jnp.maximum(dist, 0.0))
            pick = jnp.searchsorted(cdf, uniforms[j] * cdf[-1], side="right")
            pick = jnp.where(cdf[-1] > 0, jnp.minimum(pick, n - 1), spare[j])
            return seeds.at[j + 1].set(pick.astype(jnp.int32)), dist

        seeds, _ = jax.lax.fori_loop(
            0, k - 1, seed,
            (jnp.zeros((k,), jnp.int32).at[0].set(first),
             jnp.full((n,), jnp.inf, jnp.float32)),
        )
        centres = jnp.take(xt, seeds, axis=1).T  # (k, d)
        ones, flat = jnp.ones_like(centres), jnp.full((k,), 1.0 / k)
        # one Lloyd round, then the hard assignment to its means
        mass, s1, _, _ = _stats(xt, centres, ones, flat, 0.0, hard=True)
        centres = s1 / jnp.maximum(mass, 1.0)[:, None]
        mass, s1, s2, _ = _stats(xt, centres, ones, flat, 0.0, hard=True)
        inv = 1.0 / jnp.maximum(mass, 1.0)
        mu = inv[:, None] * s1
        var = inv[:, None] * s2 - mu * mu
        var_lb = _variance_floor(xt, var_floors)
        return mu, jnp.maximum(var, var_lb), mass / n, var_lb, seeds


@partial(jax.jit, static_argnames=("k",))
def _gmm_init_random(xt, draws, var_floors, *, k: int):
    """The reference's random start: means uniform in the box of the
    data, variances a tenth of its squared sides, equal weights."""
    lo, hi = jnp.min(xt, axis=1), jnp.max(xt, axis=1)
    mu = draws * (hi - lo) + lo
    var = 0.1 * jnp.ones_like(mu) * (hi - lo) ** 2
    var_lb = _variance_floor(xt, var_floors)
    return mu, jnp.maximum(var, var_lb), jnp.full((k,), 1.0 / k), var_lb


@partial(jax.jit, static_argnames=("max_iterations",))
def _gmm_em(xt, mu, var, w, var_lb, rules, *, max_iterations: int):
    """The whole EM as one device program (no host read until it ends):
    each round is one blocked pass over the sample for the cost and the
    thresholded posteriors' statistics, then the reference's tests in its
    order — stop, before the update, where the cost rose by less than
    ``stop_tolerance`` of itself or a cluster fell under
    ``min_cluster_size`` — and the M-step with the variance floor.
    ``rules`` is (stop_tolerance, min_cluster_size, weight_threshold).
    Returns (mu, var, w, rounds begun, index into STOP_REASONS)."""
    n = xt.shape[1]
    tol, floor, threshold = rules[0], rules[1], rules[2]

    def cond(state):
        return (state[0] < max_iterations) & (state[5] < 0)

    def body(state):
        i, mu, var, w, prev_cost, _ = state
        with jax.named_scope("gmm.estep"):
            q_sum, s1, s2, lse = _stats(xt, mu, var, w, threshold)
        with jax.named_scope("gmm.mstep"):
            cost = lse / n
            converged = (cost - prev_cost) < tol * jnp.abs(prev_cost)
            unbalanced = jnp.any(q_sum < floor)
            reason = jnp.where(converged, 0, jnp.where(unbalanced, 2, -1))
            inv = 1.0 / jnp.maximum(q_sum, 1e-30)
            mu_new = inv[:, None] * s1
            var_new = jnp.maximum(inv[:, None] * s2 - mu_new * mu_new, var_lb)
            keep = lambda new, old: jnp.where(reason >= 0, old, new)
            return (
                i + 1, keep(mu_new, mu), keep(var_new, var),
                keep(q_sum / n, w), keep(cost, prev_cost),
                reason.astype(jnp.int32),
            )

    i, mu, var, w, _, reason = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), mu, var, w, jnp.float32(-jnp.inf), jnp.int32(-1)),
    )
    return mu, var, w, i, jnp.where(reason < 0, 1, reason)


@dataclasses.dataclass(eq=False)
class GaussianMixtureModelEstimator(Estimator):
    """Local EM over the (collected) sample, mirroring
    GaussianMixtureModelEstimator.scala:25 parameter-for-parameter. The
    fitted model carries ``fit_info``: the rounds the EM began, why it
    stopped (one of ``STOP_REASONS``) and the k-means++ seeds' rows."""

    k: int
    max_iterations: int = 100
    min_cluster_size: int = 40
    stop_tolerance: float = 1e-4
    weight_threshold: float = 1e-4
    small_variance_threshold: float = 1e-2
    absolute_variance_threshold: float = 1e-9
    initialization_method: str = KMEANS_PLUS_PLUS_INITIALIZATION
    seed: int = 0

    def draws(self, n: int) -> tuple:
        """The host generator's part of a k-means++ start over ``n``
        rows, all at once: (the first seed's row, the k - 1 uniforms that
        place the others, the rows taken where no distance is left), in
        the order ``KMeansPlusPlusEstimator``'s seeding loop draws them."""
        rng = np.random.default_rng(self.seed)
        first = int(rng.integers(0, n))
        uniforms = rng.random(self.k - 1)
        return first, uniforms, rng.integers(0, n, self.k - 1)

    def initialize(self, xt):
        """(mu, var, weights, var_lb, seeds) of the start over the
        columns of ``xt`` (d, n); ``seeds`` is None for a random start."""
        d, n = xt.shape
        floors = jnp.asarray(
            [self.small_variance_threshold,
             self.absolute_variance_threshold], jnp.float32)
        if self.initialization_method == KMEANS_PLUS_PLUS_INITIALIZATION:
            first, uniforms, spare = self.draws(n)
            return _gmm_init(
                xt, first, jnp.asarray(uniforms, jnp.float32),
                jnp.asarray(spare, jnp.int32), floors, k=self.k,
            )
        rng = np.random.default_rng(self.seed)
        draws = jnp.asarray(rng.uniform(size=(self.k, d)), jnp.float32)
        return _gmm_init_random(xt, draws, floors, k=self.k) + (None,)

    def fit(self, data) -> GaussianMixtureModel:
        x = data.array() if isinstance(data, Dataset) else data
        xt = jnp.asarray(x, jnp.float32).T  # (d, n): rows on the lanes
        reg = get_global_registry()
        with span("gmm.init", n=xt.shape[1], k=self.k):
            mu, var, weights, var_lb, seeds = self.initialize(xt)
        with span("gmm.em", n=xt.shape[1], k=self.k):
            mu, var, weights, rounds, reason = _gmm_em(
                xt, mu, var, weights, var_lb,
                jnp.asarray([self.stop_tolerance, self.min_cluster_size,
                             self.weight_threshold], jnp.float32),
                max_iterations=self.max_iterations,
            )
            # the fit's one host read, after the loop has ended
            rounds, reason = int(rounds), STOP_REASONS[int(reason)]
        reg.counter("keystone_gmm_fits_total", "GMM fits").inc()
        reg.counter(
            "keystone_gmm_em_iterations_total",
            "EM rounds begun (each one pass over the sample)",
        ).inc(by=rounds)
        reg.counter(
            "keystone_gmm_stop_total", "why an EM stopped",
            labelnames=("reason",),
        ).inc((reason,))
        model = GaussianMixtureModel(
            mu.T, var.T, weights, self.weight_threshold
        )
        model.fit_info = {"iterations": rounds, "reason": reason,
                          "seeds": seeds}
        return model
