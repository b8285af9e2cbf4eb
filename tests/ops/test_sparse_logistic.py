"""The grouped sparse path of LogisticRegressionEstimator against a
float64 numpy reference, at a small size: 3,000 rows x 5,000 features,
ragged rows, Zipf-skewed feature ids."""

import numpy as np
import pytest
import jax.numpy as jnp
from jax.experimental import sparse as jsparse

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.ops.learning import sparse_buckets
from keystone_tpu.ops.learning.classifiers import (
    LogisticRegressionEstimator,
    LogisticRegressionModel,
)
from keystone_tpu.parallel.dataset import Dataset

N, D = 3000, 5000
CHIP_WIDTHS = (sparse_buckets.BUCKET, sparse_buckets.UNROLL)


def rows(seed, case="zipf"):
    """(row ids, column ids, values, labels): ragged rows in row order,
    distinct sorted ids a row drawn by Zipf(1) over the ranks."""
    rng = np.random.default_rng(seed)
    m = np.clip(np.round(np.exp(np.log(20) + 0.8 * rng.standard_normal(N))),
                1, 300).astype(int)
    if case == "empty_rows":
        m[rng.choice(N, 200, replace=False)] = 0
    p = 1.0 / np.arange(1, D + 1)
    p /= p.sum()
    cols = [np.sort(rng.choice(D, k, replace=False, p=p)) for k in m]
    if case == "dense_column":  # column 7 in every row
        cols = [np.union1d(c, [7]) for c in cols]
    r = np.concatenate([np.full(len(c), i) for i, c in enumerate(cols)])
    c = np.concatenate(cols)
    v = rng.uniform(0.5, 2.0, c.size).astype(np.float32)
    y = rng.integers(0, 2, N)
    return r, c, v, y


def bcoo(r, c, v, order=None):
    if order is not None:
        r, c, v = r[order], c[order], v[order]
    idx = np.stack([r, c], axis=1).astype(np.int32)
    return jsparse.BCOO((jnp.asarray(v), jnp.asarray(idx)), shape=(N, D))


def reference(r, c, v, y, W):
    """Mean softmax cross-entropy and its gradient, float64."""
    W = np.asarray(W, np.float64)
    z = np.zeros((N, W.shape[1]))
    np.add.at(z, r, v[:, None].astype(np.float64) * W[c])
    top = z.max(1, keepdims=True)
    e = np.exp(z - top)
    s = e.sum(1, keepdims=True)
    loss = np.mean(np.log(s)[:, 0] + top[:, 0] - z[np.arange(N), y])
    resid = e / s
    resid[np.arange(N), y] -= 1.0
    g = np.zeros_like(W)
    np.add.at(g, c, v[:, None] * resid[r])
    return loss, g / N


@pytest.fixture(autouse=True)
def quick_kernel(monkeypatch):
    """A narrower bucket and window unroll than the chip's: the Pallas
    interpreter runs a kernel's unrolled loops op by op, and the
    products do not depend on either (``test_bucket_product_*`` holds
    the chip's widths)."""
    monkeypatch.setattr(sparse_buckets, "BUCKET", 128)
    monkeypatch.setattr(sparse_buckets, "UNROLL", 2)


@pytest.fixture(params=[False, True], ids=["one_chunk", "many_chunks"])
def chunks(request, monkeypatch):
    """Small grid steps and sort chunks, so that a fit of this size
    crosses their boundaries as the full size does."""
    if request.param:
        monkeypatch.setattr(sparse_buckets, "STEP_REGS", 2)
        monkeypatch.setattr(sparse_buckets, "STEP", 2 * sparse_buckets.REG)
        monkeypatch.setattr(sparse_buckets, "_SORT_CHUNK", 8192)
    return request.param


@pytest.mark.parametrize("case,order", [
    ("zipf", "vectorizer"), ("zipf", "any"), ("dense_column", "vectorizer"),
    ("empty_rows", "vectorizer"), ("empty_rows", "any")])
def test_gradient_matches_float64(chunks, case, order):
    """Both directions (X·W and Xᵀ(P − Y)) through the objective and its
    gradient: 1e-5 relative, float32 sums of at most a column's 3,000
    terms against float64."""
    r, c, v, y = rows(1, case)
    perm = np.random.default_rng(2).permutation(r.size)
    x = bcoo(r, c, v, perm if order == "any" else None)
    W = np.random.default_rng(3).standard_normal((D, 2)).astype(np.float32)
    f, g = LogisticRegressionEstimator(2).value_and_grad(
        Dataset.from_array(x), Dataset.from_array(jnp.asarray(y)), W)
    want_f, want_g = reference(r, c, v, y, W)
    assert abs(float(f) - want_f) <= 1e-6 * abs(want_f)
    assert np.linalg.norm(np.asarray(g) - want_g) \
        <= 1e-5 * np.linalg.norm(want_g)


@pytest.mark.parametrize("direction", ["rows", "cols"])
@pytest.mark.parametrize("order", ["sorted", "any"])
def test_bucket_product_matches_float64(monkeypatch, direction, order):
    """The bucket kernel at the chip's bucket width and unroll, both ways
    (X·w: destination row; Xᵀ·r: destination column), each sum within a
    few float32 ulps of the sum of its terms' magnitudes."""
    monkeypatch.setattr(sparse_buckets, "BUCKET", CHIP_WIDTHS[0])
    monkeypatch.setattr(sparse_buckets, "UNROLL", CHIP_WIDTHS[1])
    r, c, v, _ = rows(10)
    keep = np.random.default_rng(11).random(r.size) < 0.25
    r, c, v = r[keep], c[keep], v[keep]
    if order == "any":
        perm = np.random.default_rng(12).permutation(r.size)
        r, c, v = r[perm], c[perm], v[perm]
    dst_col = 0 if direction == "rows" else 1
    dst, src = (r, c) if dst_col == 0 else (c, r)
    n_dst, n_src = (N, D) if dst_col == 0 else (D, N)
    table = np.random.default_rng(13).standard_normal(n_src).astype(
        np.float32)
    idx = jnp.asarray(np.stack([r, c], 1).astype(np.int32))
    layout = sparse_buckets.bucket_layout(jnp.asarray(v), idx, dst_col,
                                          n_dst, n_src)
    got = np.asarray(sparse_buckets.bucket_product(
        layout, jnp.asarray(table), n_dst))
    terms = v.astype(np.float64) * table[src]
    want = np.bincount(dst, terms, n_dst)
    scale = np.bincount(dst, np.abs(terms), n_dst)
    assert (np.abs(got - want) <= 1e-6 * scale).all()


@pytest.mark.parametrize("k", [2, 3])
def test_scores_match_float64(chunks, k):
    """The model's class scores on BCOO rows: one product a class."""
    r, c, v, _ = rows(4)
    W = np.random.default_rng(5).standard_normal((D, k)).astype(np.float32)
    model = LogisticRegressionModel(jnp.asarray(W))
    got = np.asarray(model.scores_batch(Dataset.from_array(bcoo(r, c, v)))
                     .array())
    want = np.zeros((N, k))
    np.add.at(want, r, v[:, None].astype(np.float64) * W[c])
    assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)
    classes = np.asarray(model.apply_batch(
        Dataset.from_array(bcoo(r, c, v))).array())
    assert (classes == got.argmax(1)).all()


def plain_lbfgs(fg, w, iters, m=10, tol=1e-4):
    """Two-loop L-BFGS with Armijo halving (c1 1e-4, 30 tries), the
    relative-improvement stop; float64. Returns (w, iterations)."""
    f, g = fg(w)
    S, Y = [], []
    for it in range(iters):
        q = g.copy()
        al = []
        for s, yv in reversed(list(zip(S, Y))):
            a = (s @ q) / (yv @ s)
            al.append(a)
            q -= a * yv
        if S:
            q *= (S[-1] @ Y[-1]) / (Y[-1] @ Y[-1])
        for (s, yv), a in zip(zip(S, Y), reversed(al)):
            q += (a - (yv @ q) / (yv @ s)) * s
        dr = -q
        dg = dr @ g
        if dg >= 0:
            dr, dg = -g, -(g @ g)
        step = 1.0
        for _ in range(30):
            fn, gn = fg(w + step * dr)
            if fn <= f + 1e-4 * step * dg:
                break
            step *= 0.5
        else:
            return w, it
        s, yv = step * dr, gn - g
        if s @ yv > 1e-10:
            S.append(s)
            Y.append(yv)
            S, Y = S[-m:], Y[-m:]
        done = abs(f - fn) / max(abs(f), abs(fn), 1.0) < tol
        w, f, g = w + s, fn, gn
        if done:
            return w, it + 1
    return w, iters


def test_fit_matches_plain_lbfgs():
    """A whole 20-iteration fit against the plain two-loop L-BFGS on the
    float64 objective: the objectives agree to 1e-4 relative — the
    optimizer's float32 history and line search against float64 ones
    drift by a few 1e-6 an iteration on this conditioning — and both
    stop at the same iteration, or one apart."""
    r, c, v, y = rows(6)
    x = bcoo(r, c, v)
    reg = get_global_registry()
    iters = reg.counter("keystone_solver_lbfgs_iterations_total", "")
    before = iters.get()
    model = LogisticRegressionEstimator(2, num_iters=20).fit(
        Dataset.from_array(x), Dataset.from_array(jnp.asarray(y)))
    got_iters = iters.get() - before
    f_got, _ = reference(r, c, v, y, model.W)

    def fg(w):
        f, g = reference(r, c, v, y, w.reshape(D, 2))
        return f, g.ravel()

    w, want_iters = plain_lbfgs(fg, np.zeros(D * 2), 20)
    f_want, _ = fg(w)
    assert f_got < reference(r, c, v, y, np.zeros((D, 2)))[0]
    assert abs(f_got - f_want) <= 1e-4 * f_want
    assert abs(got_iters - want_iters) <= 1


def test_counters_count_the_fit():
    r, c, v, y = rows(7)
    x = bcoo(r, c, v)
    reg = get_global_registry()
    names = ("keystone_solver_logreg_fits_total",
             "keystone_solver_sparse_nnz_total",
             "keystone_solver_lbfgs_iterations_total",
             "keystone_solver_lbfgs_evaluations_total")
    before = {k: reg.counter(k, "").get() for k in names}
    path = reg.counter("keystone_solver_logreg_path_total", "",
                       labelnames=("path",))
    grouped = path.get(("grouped",))
    LogisticRegressionEstimator(2, num_iters=5).fit(
        Dataset.from_array(x), Dataset.from_array(jnp.asarray(y)))
    got = {k: reg.counter(k, "").get() - before[k] for k in names}
    assert got["keystone_solver_logreg_fits_total"] == 1
    assert path.get(("grouped",)) - grouped == 1
    assert got["keystone_solver_sparse_nnz_total"] == r.size
    assert 1 <= got["keystone_solver_lbfgs_iterations_total"] <= 5
    # one evaluation at the start and at least one an iteration
    assert got["keystone_solver_lbfgs_evaluations_total"] \
        >= got["keystone_solver_lbfgs_iterations_total"] + 1


def test_dense_rows_count_the_dense_path():
    reg = get_global_registry()
    path = reg.counter("keystone_solver_logreg_path_total", "",
                       labelnames=("path",))
    dense = path.get(("dense",))
    X = np.random.default_rng(8).standard_normal((64, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    LogisticRegressionEstimator(2, num_iters=3).fit(
        Dataset.from_array(jnp.asarray(X)), Dataset.from_array(jnp.asarray(y)))
    assert path.get(("dense",)) - dense == 1


def test_out_of_range_label_poisons_the_sparse_fit():
    """Labels are checked on the device: an out-of-range label gives a
    NaN model, not a row silently dropped."""
    r, c, v, y = rows(9)
    y = y.copy()
    y[0] = 5
    model = LogisticRegressionEstimator(2, num_iters=2).fit(
        Dataset.from_array(bcoo(r, c, v)), Dataset.from_array(jnp.asarray(y)))
    assert np.isnan(np.asarray(model.W)).any()
