"""Plain reference of the amazon-reviews-logistic configuration
(AmazonReviewsPipeline.scala's LogisticRegressionEstimator(numClasses 2,
numIters 20): MLlib's LogisticGradient, softmax cross-entropy, mean over
the rows, SquaredL2Updater at regParam 0).

Everything in float64 on the host, from the benchmark's own rows (a
``scipy.sparse`` CSR matrix of the BCOO's (row, column, value) entries,
duplicates summed): logits X·W, the mean loss log Σ_c exp(z_c) − z_y,
the gradient Xᵀ(P − Y) / n. Nothing is imported from keystone_tpu and
nothing the program made is read but what is compared: each kept
model's W, its held-out class scores, and the program's own gradient at
W = 0 and at W.

A float64 fit of its own (20 iterations, ~25 evaluations of 358M
non-zeros) would take minutes of the host; the fit is held instead to
its gradient at two points and its progress: ``objective_ratio``, the
objective at W over the objective at 0, which a fit stopped early or
stepping the wrong way reads high.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err


def matrix(indices, values, rows: int, d: int):
    import scipy.sparse as sp

    idx = np.asarray(indices)
    return sp.csr_matrix(
        (np.asarray(values, np.float64), (idx[:, 0], idx[:, 1])),
        shape=(rows, d))


def objective(X, y, W, residual=None):
    """(mean loss, gradient, |X|ᵀ max(|P − Y|, 2⁻²⁴) / n) in float64;
    ``residual`` rounds P − Y (the control's departure). The floor is
    float32's resolution of a probability: a residual p − y computed
    from a float32 p is exact to no better than that, however small."""
    W = np.asarray(W, np.float64)
    n, k = X.shape[0], W.shape[1]
    z = X @ W
    top = z.max(axis=1, keepdims=True)
    ez = np.exp(z - top)
    s = ez.sum(axis=1, keepdims=True)
    logz = np.log(s)[:, 0] + top[:, 0]
    loss = float(np.mean(logz - z[np.arange(n), y]))
    r = ez / s
    r[np.arange(n), y] -= 1.0
    if residual is not None:
        r = residual(r)
    scale = np.asarray(abs(X).T @ np.maximum(np.abs(r), 2.0 ** -24)) / n
    return loss, np.asarray(X.T @ r) / n, scale


def backward_err(got, want, scale) -> float:
    """The largest error of a gradient's entries, each over the float64
    sum of its terms' magnitudes (``scale``): the error a float32 sum
    leaves is a few ulps of that sum, however far its terms cancel, so
    the measure sees the arithmetic's precision where the relative
    error of the whole gradient, dominated by hot columns whose
    millions of residuals cancel, does not."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    live = scale > 0
    return float(np.max(np.abs(got - want)[live] / scale[live]))


def _matrices(ctx, sample):
    """The training and held-out CSR matrices, made once a sample (the
    control reads them again after ``compare``)."""
    if "matrices" not in sample:
        d = int(ctx.config["num_features"])
        sample["matrices"] = (
            matrix(sample["indices"], sample["values"], sample["rows"], d),
            matrix(sample["test_indices"], sample["test_values"],
                   sample["test_rows"], d))
    return sample["matrices"]


def _kept(sample):
    return sample["outputs"].values()


def compare(ctx, sample: dict) -> dict:
    """Worst over the kept models. ``grad_backward_err``: the program's
    gradient against the float64 one at W = 0 and at the model's W
    (``backward_err``, the worse); ``scores_rel_err``: the held-out
    class scores against the float64 X_test·W (relative Frobenius);
    ``objective_ratio``: the float64 objective at W over that at 0."""
    X, Xt = _matrices(ctx, sample)
    y = np.asarray(sample["y"]).reshape(-1)
    outs = list(_kept(sample))
    if not outs:
        return {"grad_backward_err": float("inf")}
    k = outs[0]["W"].shape[1]
    f0, g0, a0 = objective(X, y, np.zeros((X.shape[1], k)))
    worst = dict.fromkeys(
        ("grad_backward_err", "scores_rel_err", "objective_ratio"), 0.0)
    for o in outs:
        f1, g1, a1 = objective(X, y, o["W"])
        got = {
            "grad_backward_err": max(backward_err(o["grad0"], g0, a0),
                                     backward_err(o["grad"], g1, a1)),
            "scores_rel_err": rel_err(
                o["scores"], Xt @ np.asarray(o["W"], np.float64)),
            "objective_ratio": f1 / f0,
        }
        for key, v in got.items():
            worst[key] = max(worst[key], v if v == v else float("inf"))
    return worst


def control(ctx, sample: dict) -> dict:
    """The reference one precision step down (bfloat16, the nearest below
    the configuration's float32), each read by the measure it targets
    against the float64 reference: the gradient at each kept model's W
    with the residuals P − Y rounded (``grad_backward_err``), the
    held-out scores with W rounded (``scores_rel_err``)."""
    import jax.numpy as jnp

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                          .astype(jnp.float32), np.float64)

    X, Xt = _matrices(ctx, sample)
    y = np.asarray(sample["y"]).reshape(-1)
    grad = scores = 0.0
    for o in _kept(sample):
        _, g, a = objective(X, y, o["W"])
        grad = max(grad, backward_err(
            objective(X, y, o["W"], residual=bf16)[1], g, a))
        W = np.asarray(o["W"], np.float64)
        scores = max(scores, rel_err(Xt @ bf16(W), Xt @ W))
    return {"bfloat16_residuals": {"grad_backward_err": grad},
            "bfloat16_weights": {"scores_rel_err": scores}}
