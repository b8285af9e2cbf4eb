"""AmazonReviewsPipeline's fit, through
``LogisticRegressionEstimator(2, num_iters=20).fit`` on the BCOO rows that
``SparseFeatureVectorizer.apply_batch`` emits after
``CommonSparseFeatures``, and the fitted model's ``apply_batch``.

The rows are made on the device from the seed (the host's text
featurization is bypassed): a row's count of distinct n-gram features is
lognormal, rounded and clipped; its feature ids are Zipf-distributed over
the frequency ranks, drawn with replacement, sorted, and made distinct by
raising a repeated id to the next free rank (the least strictly
increasing sequence above the sorted draws), held under
``num_features``; every value is 1.0 (TermFrequency(x => 1)). Labels: 1
where a seeded teacher's score of the row — a standard normal weight a
feature summed over the row, over the root of the row's count, plus a
standard normal — is above the score's (1 - positive_share) quantile.
The constants are the cell's ``traffic`` and the configuration's
``assumed``.
"""

from __future__ import annotations

import gc
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.programs import fold_key


def lengths(key, n: int, tr: dict):
    """(n,) int32 distinct features a row: lognormal, rounded, clipped."""

    lo, hi = (int(v) for v in tr["nnz_clip"])
    z = jax.random.normal(key, (n,))
    m = jnp.round(jnp.exp(np.log(float(tr["nnz_median"]))
                          + float(tr["nnz_sigma"]) * z))
    return jnp.clip(m, lo, hi).astype(jnp.int32)


def zipf_ranks(u, d: int, s: float):
    """Ranks 0..d-1 from uniforms by the continuous power law's inverse
    CDF: P(rank >= k) falls as k^(1-s), and as log for s = 1."""

    if abs(s - 1.0) < 1e-12:
        x = jnp.exp(u * np.log(d + 1.0))
    else:
        a = 1.0 - s
        x = (1.0 + u * ((d + 1.0) ** a - 1.0)) ** (1.0 / a)
    return jnp.clip(jnp.floor(x) - 1.0, 0, d - 1).astype(jnp.int32)


@partial(jax.jit, static_argnums=(1,))
def _row_ids(offsets, e: int):
    start = jnp.zeros((e,), jnp.int32).at[offsets].set(1, mode="drop")
    return jnp.cumsum(start) - 1


@jax.jit
def _within(offsets, m, row):
    """(index within the row, the row's count) of every entry."""
    first = jnp.take(offsets, row)
    return jnp.arange(row.shape[0], dtype=jnp.int32) - first, \
        jnp.take(m, row)


@jax.jit
def _quantized_run(key, row):
    q = (jax.random.exponential(key, row.shape) * 32768.0) \
        .astype(jnp.int32) + 1
    return jnp.cumsum(q)  # wraps past 2^31; differences stay exact


@partial(jax.jit, static_argnums=(5, 6))
def _sorted_draws(key, offsets, m, row, run, d: int, s: float):
    base = jnp.where(offsets > 0, run[jnp.maximum(offsets - 1, 0)], 0)
    extra = (jax.random.exponential(key, m.shape) * 32768.0) \
        .astype(jnp.int32) + 1
    total = (run[offsets + m - 1] - base + extra).astype(jnp.float32)
    u = (run - jnp.take(base, row)).astype(jnp.float32) \
        / jnp.take(total, row)
    return zipf_ranks(u, d, s)


@jax.jit
def _raised(c, i):
    """The least strictly increasing sequence above sorted draws within
    each row (a repeated id raised to the next free rank)."""
    head = i == 0

    def step(st):
        col = st[0]
        prev = jnp.concatenate([jnp.full((1,), -1, col.dtype), col[:-1]])
        new = jnp.where(head, col, jnp.maximum(col, prev + 1))
        return new, jnp.any(new != col)

    return jax.lax.while_loop(lambda st: st[1], step,
                              (c, jnp.bool_(True)))[0]


@partial(jax.jit, static_argnums=(4,))
def _indices(row, up, i, count, d: int):
    return jnp.stack([row, jnp.minimum(up, d - count + i)], axis=1)


def rows_of(key, m, e: int, d: int, s: float):
    """The BCOO indices (e, 2) of rows with ``m`` features each (e =
    sum(m)): row ids in order, distinct sorted columns within a row,
    as a few programs (one would take the TPU compiler minutes).
    A row's draws come sorted, without a sort: its uniforms are the
    partial sums of m + 1 exponentials over their total (the order
    statistics of m uniforms; the exponentials quantized to 2^-15, so
    that one int32 running sum over all rows gives every row's exactly),
    mapped through the monotone inverse CDF; a repeated id is raised to
    the next free rank, and the row's last ids held under d (count - i
    entries are still to place)."""
    offsets = jnp.cumsum(m) - m
    ke, kx = jax.random.split(key)
    row = _row_ids(offsets, e)
    run = _quantized_run(ke, row)
    c = _sorted_draws(kx, offsets, m, row, run, d, s)
    del run
    i, count = _within(offsets, m, row)
    up = _raised(c, i)
    del c
    return _indices(row, up, i, count, d)


@jax.jit
def _row_scores(t, idx):
    return jnp.cumsum(jnp.take(t, idx[:, 1]))


@partial(jax.jit, static_argnums=(4, 5))
def _teacher(kt, kz, idx, m, d: int, share: float):
    """Labels: integer weights (a standard normal a feature, in 1/1024ths)
    and one int32 running sum give each row's score exactly."""
    t = jnp.round(jax.random.normal(kt, (d,)) * 1024.0).astype(jnp.int32)
    run = _row_scores(t, idx)
    last = jnp.cumsum(m) - 1
    before = jnp.where(last - m >= 0, run[jnp.maximum(last - m, 0)], 0)
    score = (run[last] - before).astype(jnp.float32) / 1024.0 \
        / jnp.sqrt(m.astype(jnp.float32))
    score = score + jax.random.normal(kz, m.shape)
    cut = jnp.quantile(score, 1.0 - share)
    return (score > cut).astype(jnp.int32)


def draw(key, cfg: dict, tr: dict, n: int):
    """(indices, values, labels, nnz) of n rows from one key."""
    d = int(cfg["num_features"])
    s = float(tr["zipf_exponent"])
    km, kc, kt, kz = jax.random.split(key, 4)
    m = jax.jit(lengths, static_argnums=(1, 2))(km, n, _frozen(tr))
    e = int(jnp.sum(m))
    idx = rows_of(kc, m, e, d, s)
    vals = jnp.ones((e,), jnp.float32)
    y = _teacher(kt, kz, idx, m, d, float(tr["positive_share"]))
    return idx, vals, y, e


class _frozen(dict):
    """A hashable traffic dict, for a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _check_program():
    """The estimator's grouped sparse path: a tree without it cannot run
    this cell (its BCOO products at 358M non-zeros do not compile)."""
    from keystone_tpu.ops.learning import classifiers

    if not hasattr(classifiers, "SparseLogisticLayout"):
        raise SystemExit(
            "benchmark.programs.amazon: this keystone_tpu has no grouped "
            "sparse layout for LogisticRegressionEstimator; its BCOO "
            "products cannot run 4,062,500 rows of 100,000 features")


def make_inputs(ctx) -> dict:
    from jax.experimental import sparse as jsparse

    _check_program()
    cfg, tr = ctx.config, ctx.traffic
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    n_test = int(tr["heldout_rows"])
    d = int(cfg["num_features"])
    if int(cfg["num_classes"]) != 2:
        raise ValueError("AmazonReviewsPipeline fits 2 classes")
    key = fold_key(ctx.seed)

    ktr, kte = jax.random.split(key)
    idx, vals, y, e = draw(ktr, cfg, tr, n)
    tidx, tvals, ty, te = draw(kte, cfg, tr, n_test)
    x = jsparse.BCOO((vals, idx), shape=(n, d))
    xt = jsparse.BCOO((tvals, tidx), shape=(n_test, d))
    return {"x": x, "y": y, "x_test": xt, "y_test": ty, "rows": n,
            "nnz": e, "config": cfg}


def estimator(cfg: dict):
    from keystone_tpu.ops.learning.classifiers import (
        LogisticRegressionEstimator,
    )

    return LogisticRegressionEstimator(
        int(cfg["num_classes"]), num_iters=int(cfg["num_iters"]),
        reg_param=float(cfg["reg_param"]),
        convergence_tol=float(cfg["convergence_tol"]))


def fit(inputs: dict):
    """One whole fit, as the pipeline's estimator makes it, ended by
    block_until_ready on the model."""

    from keystone_tpu.parallel.dataset import Dataset

    model = estimator(inputs["config"]).fit(
        Dataset.from_array(inputs["x"], n=inputs["rows"]),
        Dataset.from_array(inputs["y"]))
    jax.block_until_ready(model.W)
    return model


def outputs(fitted, inputs: dict) -> dict:
    """The fitted model's classes of the held-out rows (``apply_batch``)
    and their class scores, the model, and the program's own gradient
    at W = 0 and at the model."""

    from keystone_tpu.parallel.dataset import Dataset

    test = Dataset.from_array(inputs["x_test"])
    est = estimator(inputs["config"])
    data = Dataset.from_array(inputs["x"], n=inputs["rows"])
    labels = Dataset.from_array(inputs["y"])
    _, g0 = est.value_and_grad(data, labels, jnp.zeros_like(fitted.W))
    _, g1 = est.value_and_grad(data, labels, fitted.W)
    return {
        "classes": np.asarray(fitted.apply_batch(test).array()),
        "scores": np.asarray(fitted.scores_batch(test).array()),
        "W": np.asarray(fitted.W),
        "grad0": np.asarray(g0), "grad": np.asarray(g1),
    }


def reference_inputs(inputs: dict) -> dict:
    """Host copies of the benchmark's own data."""
    x, xt = inputs["x"], inputs["x_test"]
    return {"indices": np.asarray(x.indices), "values": np.asarray(x.data),
            "y": np.asarray(inputs["y"]), "rows": inputs["rows"],
            "test_indices": np.asarray(xt.indices),
            "test_values": np.asarray(xt.data),
            "test_rows": xt.shape[0]}


def free(inputs: dict) -> None:
    inputs.clear()
    gc.collect()
