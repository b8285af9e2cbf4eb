"""Plain reference of the flagship's fit from Fisher vectors to model
(ImageNetSiftLcsFV.scala:136-143 with BlockWeightedLeastSquares.scala:36,
102-320): one block of all the features, one pass, from a zero model.

With w the mixture weight, n_c a class's rows, mu and Cov the population
mean and covariance of the features, mu_c and Cov_c a class's own, and
d_c = mu_c - mu, each class c has the system

    A_c W_c = rhs_c
    A_c   = (1-w) Cov + w Cov_c + w(1-w) d_c d_c' + lambda I
    rhs_c = (1-w) X'R_c / n + w X_c'R_cc / n_c - jointMean_c mmw_c
    jointMean_c = w mu_c + (1-w) mu
    mmw_c = (1-w) mean(R_c) + w mean(R_cc)

where R_c is the zero model's residual in class c's column: the +-1
indicator less jointLabelMean_c = 2w + 2(1-w) n_c/n - 1, so ``hit`` on
the class's own rows (R_cc) and ``miss`` on the others, and X'R_c is a
sum over each of the two groups of rows. The intercept is
jointLabelMean_c - jointMean_c . W_c. Everything is numpy in float64 on
the host, formed directly from host copies of the benchmark's features
and labels; nothing of keystone_tpu is imported or read.

All of the classes' direct solves (a Cholesky of b x b each) would cost
minutes, so the comparison has two parts:

(a) ``scores_rel_err``: for ``check_classes`` classes drawn from the
    seed, each system is assembled and solved by Cholesky, and the
    program's scores of the held-out rows in those classes (read before
    its TopKClassifier) are compared with the direct solution's, as a
    relative Frobenius error, the worst of the kept models;
(b) ``system_rel_residual``: for every class that has rows, the worst
    ||A_c W_c - rhs_c|| / ||rhs_c|| of the program's model under this
    file's operator, applied matrix-free, class by class.

``control`` puts three departures in the program's place, each solved
here for the drawn classes alone (a worst over those is a lower bound of
the worst over all, so a departure that fails on them fails): the
features rounded to bfloat16 before the solve, the class term left out
(w = 0), and a conjugate-gradient solve stopped at 1e-3.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err

ROWS = 16384  # rows taken to float64 at a time


class Moments:
    """Rows sorted by class (so that a class's rows are one slice), and
    the population's and the classes' first and second moments."""

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int):
        n, b = x.shape
        order = np.argsort(y, kind="stable")
        self.xs = x[order]
        self.n, self.b, self.num_classes = n, b, num_classes
        self.counts = np.bincount(y, minlength=num_classes).astype(np.int64)
        self.ends = np.cumsum(self.counts)
        gram = np.zeros((b, b), np.float64)
        for s in range(0, n, ROWS):
            xc = self.xs[s:s + ROWS].astype(np.float64)
            gram += xc.T @ xc
        self.class_sum = np.stack([
            self.rows_of(c).sum(axis=0) for c in range(num_classes)])
        self.pop_mean = self.class_sum.sum(axis=0) / n
        self.pop_cov = gram / n - np.outer(self.pop_mean, self.pop_mean)
        self.class_mean = self.class_sum / np.maximum(self.counts, 1)[:, None]

    def rows_of(self, c: int) -> np.ndarray:
        """Class c's rows in float64."""
        return self.xs[self.ends[c] - self.counts[c]:self.ends[c]].astype(
            np.float64)


def joint_label_mean(m: Moments, c: int, w: float) -> float:
    return 2.0 * w + 2.0 * (1.0 - w) * m.counts[c] / m.n - 1.0


def joint_mean(m: Moments, c: int, w: float) -> np.ndarray:
    return w * m.class_mean[c] + (1.0 - w) * m.pop_mean


def right_hand_side(m: Moments, c: int, w: float) -> np.ndarray:
    n, n_c = m.n, m.counts[c]
    jlm = joint_label_mean(m, c, w)
    hit, miss = 1.0 - jlm, -1.0 - jlm  # the residual on and off the class
    others = n * m.pop_mean - m.class_sum[c]  # the other classes' rows, summed
    pop_xtr = (hit * m.class_sum[c] + miss * others) / n
    class_xtr = hit * m.class_sum[c] / n_c
    residual_mean = (hit * n_c + miss * (n - n_c)) / n
    mmw = (1.0 - w) * residual_mean + w * hit
    return (1.0 - w) * pop_xtr + w * class_xtr - joint_mean(m, c, w) * mmw


def apply_system(m: Moments, c: int, v: np.ndarray, w: float, lam: float,
                 pop_cov_v: np.ndarray) -> np.ndarray:
    """A_c v, matrix-free; ``pop_cov_v`` is Cov v, which the caller
    forms for all classes in one product."""
    xc, mu_c = m.rows_of(c), m.class_mean[c]
    d = mu_c - m.pop_mean
    cov_c_v = xc.T @ (xc @ v) / m.counts[c] - mu_c * (mu_c @ v)
    return ((1.0 - w) * pop_cov_v + w * cov_c_v
            + w * (1.0 - w) * d * (d @ v) + lam * v)


def system_matrix(m: Moments, c: int, w: float, lam: float) -> np.ndarray:
    xc, mu_c = m.rows_of(c), m.class_mean[c]
    d = mu_c - m.pop_mean
    cov_c = xc.T @ xc / m.counts[c] - np.outer(mu_c, mu_c)
    a = (1.0 - w) * m.pop_cov + w * cov_c + w * (1.0 - w) * np.outer(d, d)
    a[np.diag_indices_from(a)] += lam
    return a


def intercept(m: Moments, c: int, w: float, w_c: np.ndarray) -> float:
    return joint_label_mean(m, c, w) - joint_mean(m, c, w) @ w_c


def direct_solution(m: Moments, classes, w: float, lam: float) -> tuple:
    """(W (b, k), intercept (k,)) of the listed classes, each system
    assembled and solved by Cholesky."""
    from scipy.linalg import cho_factor, cho_solve

    cols, icpt = [], []
    for c in classes:
        a = system_matrix(m, c, w, lam)
        w_c = cho_solve(cho_factor(a, lower=True, overwrite_a=True),
                        right_hand_side(m, c, w))
        cols.append(w_c)
        icpt.append(intercept(m, c, w, w_c))
    return np.stack(cols, axis=1), np.asarray(icpt)


def cg_solution(m: Moments, classes, w: float, lam: float,
                tol: float) -> tuple:
    """The same systems by conjugate gradients, preconditioned by
    (1-w) Cov + lambda I, stopped once every class's relative residual
    is at or under ``tol``."""
    from scipy.linalg import cho_factor, cho_solve

    pre = (1.0 - w) * m.pop_cov
    pre[np.diag_indices_from(pre)] += lam
    factor = cho_factor(pre, lower=True)

    def apply(v):
        pv = m.pop_cov @ v
        return np.stack([apply_system(m, c, v[:, j], w, lam, pv[:, j])
                         for j, c in enumerate(classes)], axis=1)

    rhs = np.stack([right_hand_side(m, c, w) for c in classes], axis=1)
    x = np.zeros_like(rhs)
    r = rhs.copy()
    z = cho_solve(factor, r)
    p, rz = z.copy(), np.sum(r * z, axis=0)
    norms = np.linalg.norm(rhs, axis=0)
    for _ in range(1000):
        if np.max(np.linalg.norm(r, axis=0) / norms) <= tol:
            break
        ap = apply(p)
        alpha = rz / np.sum(p * ap, axis=0)
        x += alpha * p
        r -= alpha * ap
        z = cho_solve(factor, r)
        rz_new = np.sum(r * z, axis=0)
        p = z + (rz_new / rz) * p
        rz = rz_new
    icpt = np.asarray([intercept(m, c, w, x[:, j])
                       for j, c in enumerate(classes)])
    return x, icpt


def system_rel_residual(m: Moments, classes, model: np.ndarray, w: float,
                        lam: float) -> float:
    """The worst ||A_c W_c - rhs_c|| / ||rhs_c|| over ``classes``, of
    ``model``'s columns (one per listed class)."""
    model = np.asarray(model, np.float64)
    if model.shape != (m.b, len(classes)) or not np.all(np.isfinite(model)):
        return float("inf")
    pv = m.pop_cov @ model
    worst = 0.0
    for j, c in enumerate(classes):
        rhs = right_hand_side(m, c, w)
        got = apply_system(m, c, model[:, j], w, lam, pv[:, j])
        worst = max(worst, float(
            np.linalg.norm(got - rhs) / np.linalg.norm(rhs)))
    return worst


class Prepared:
    """What ``compare`` and ``control`` share of one sample: the
    moments, the drawn classes and their direct solution's scores."""

    def __init__(self, ctx, sample: dict):
        cfg = ctx.config
        if int(cfg["num_iter"]) != 1 or (
                int(cfg["num_features"]) > int(cfg["block_size"])):
            raise ValueError("this reference is of one block and one pass")
        self.w, self.lam = float(cfg["mixture_weight"]), float(cfg["lambda"])
        self.m = Moments(sample["x"], sample["y"], int(cfg["num_classes"]))
        self.present = np.flatnonzero(self.m.counts > 0)
        k = min(int(ctx.traffic["check_classes"]), len(self.present))
        self.drawn = np.sort(np.random.default_rng(ctx.seed).choice(
            self.present, size=k, replace=False))
        self.x_test = sample["x_test"].astype(np.float64)
        self.want = self.scores(
            *direct_solution(self.m, self.drawn, self.w, self.lam))

    def scores(self, model: np.ndarray, icpt: np.ndarray) -> np.ndarray:
        return self.x_test @ model + icpt

    def numbers(self, scores_drawn, model, classes) -> dict:
        """The cell's two numbers of one model: its scores in the drawn
        classes, and its columns of ``classes`` under this operator."""
        return {
            "scores_rel_err": rel_err(scores_drawn, self.want),
            "system_rel_residual": system_rel_residual(
                self.m, classes, model, self.w, self.lam),
        }


def prepared(ctx, sample: dict) -> Prepared:
    """One ``Prepared`` per sample, kept on the sample: the Gram costs
    most of a comparison and ``control`` follows ``compare``."""
    if "_prepared" not in sample:
        sample["_prepared"] = Prepared(ctx, sample)
    return sample["_prepared"]


def compare(ctx, sample: dict) -> dict:
    """The worst of the kept models' two numbers: (a) over the drawn
    classes, (b) over every class that has rows."""
    if not sample["outputs"]:
        return {"scores_rel_err": float("inf"),
                "system_rel_residual": float("inf")}
    p = prepared(ctx, sample)
    worst = {"scores_rel_err": 0.0, "system_rel_residual": 0.0}
    for out in sample["outputs"].values():
        model = np.asarray(out["W"], np.float64)
        if model.ndim != 2 or model.shape[1] != p.m.num_classes:
            model = np.full((p.m.b, p.m.num_classes), np.nan)
        got = p.numbers(np.asarray(out["scores"])[:, p.drawn],
                        model[:, p.present], p.present)
        worst = {k: max(worst[k], got[k]) for k in worst}
    return worst


def round_to_bfloat16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def control(ctx, sample: dict) -> dict:
    """The two numbers of each of three departures, solved here for the
    drawn classes and measured as the program's model is: each has to
    come out as not correct by at least one limit."""
    p = prepared(ctx, sample)
    low = Moments(round_to_bfloat16(sample["x"]), sample["y"],
                  p.m.num_classes)
    departures = {
        "bfloat16_features": direct_solution(low, p.drawn, p.w, p.lam),
        "no_class_term": direct_solution(p.m, p.drawn, 0.0, p.lam),
        "cg_stopped_at_1e-3": cg_solution(p.m, p.drawn, p.w, p.lam, 1e-3),
    }
    return {name: p.numbers(p.scores(model, icpt), model, p.drawn)
            for name, (model, icpt) in departures.items()}
