"""Weighted least-squares tests against a direct numpy f64 translation of
the reference algorithm (reference: BlockWeightedLeastSquaresSuite —
distributed vs local solutions on CSV fixtures, incl. shuffled variants)."""

import numpy as np
import pytest

from keystone_tpu.ops.learning.weighted_ls import (
    BlockWeightedLeastSquaresEstimator,
    PerClassWeightedLeastSquaresEstimator,
)
from keystone_tpu.parallel.dataset import Dataset


def ref_block_weighted_bcd(X, Y, block_size, num_iter, lam, w, conds=None):
    """numpy f64 translation of BlockWeightedLeastSquares.scala:139-314.
    ``conds``: a list that takes each solved system's condition number."""
    X = X.astype(np.float64)
    Y = Y.astype(np.float64)
    n, D = X.shape
    C = Y.shape[1]
    class_of = Y.argmax(1)
    counts = np.bincount(class_of, minlength=C)
    jlm = 2 * w + 2 * (1 - w) * counts / n - 1
    R = Y - jlm[None, :]
    blocks = [(s, min(s + block_size, D)) for s in range(0, D, block_size)]
    W = np.zeros((D, C))
    jm_full = np.zeros((C, D))
    for _ in range(num_iter):
        for (s, e) in blocks:
            Xb = X[:, s:e]
            res_mean = R.mean(0)
            pop_mean = Xb.mean(0)
            pop_cov = Xb.T @ Xb / n - np.outer(pop_mean, pop_mean)
            pop_xtr = Xb.T @ R / n
            delta = np.zeros((e - s, C))
            for c in range(C):
                rows = class_of == c
                Xc = Xb[rows]
                nc = counts[c]
                cmean = Xc.mean(0)
                Xz = Xc - cmean
                ccov = Xz.T @ Xz / nc
                rl = R[rows, c]
                cxtr = Xc.T @ rl / nc
                md = cmean - pop_mean
                jxtx = (
                    pop_cov * (1 - w)
                    + ccov * w
                    + np.outer(md, md) * (1 - w) * w
                )
                mmw = res_mean[c] * (1 - w) + w * rl.mean()
                jm = cmean * w + pop_mean * (1 - w)
                jxtr = pop_xtr[:, c] * (1 - w) + cxtr * w - jm * mmw
                A = jxtx + lam * np.eye(e - s)
                delta[:, c] = np.linalg.solve(A, jxtr - W[s:e, c] * lam)
                if conds is not None:
                    conds.append(np.linalg.cond(A))
                jm_full[c, s:e] = jm
            W[s:e] += delta
            R = R - Xb @ delta
    b = jlm - np.einsum("cd,dc->c", jm_full, W)
    return W, b


def _weighted_problem(n=90, D=10, C=3, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, C, n)
    centers = rng.standard_normal((C, D)) * 2
    X = (centers[y] + rng.standard_normal((n, D))).astype(np.float32)
    Y = (2.0 * np.eye(C, dtype=np.float32)[y] - 1.0)
    return X, Y, y


@pytest.mark.parametrize("num_iter,block_size", [(1, 10), (2, 4)])
def test_block_weighted_matches_reference_translation(
    mesh8, num_iter, block_size
):
    X, Y, _ = _weighted_problem()
    lam, w = 0.1, 0.6
    est = BlockWeightedLeastSquaresEstimator(
        block_size, num_iter, lam, w, class_chunk=2
    )
    model = est.fit(Dataset.of(X).shard(), Dataset.of(Y).shard())
    W_ref, b_ref = ref_block_weighted_bcd(X, Y, block_size, num_iter, lam, w)
    np.testing.assert_allclose(np.asarray(model.W), W_ref, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(model.intercept), b_ref, atol=2e-2
    )


def test_block_weighted_classifies(mesh8):
    X, Y, y = _weighted_problem(n=120, D=8, C=3, seed=1)
    est = BlockWeightedLeastSquaresEstimator(8, 2, 0.01, 0.5)
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    pred = np.asarray(model.apply_batch(Dataset.of(X)).array())
    assert (pred.argmax(1) == y).mean() > 0.95


def test_block_weighted_weight():
    assert BlockWeightedLeastSquaresEstimator(10, 3, 0.1, 0.5).weight == 10


def test_per_class_weighted_close_to_block_weighted(mesh8):
    """Both solvers optimize the same mixture-weighted objective; with
    enough sweeps they land close on a well-conditioned problem."""
    X, Y, y = _weighted_problem(n=100, D=6, C=2, seed=2)
    lam, w = 0.05, 0.5
    m1 = BlockWeightedLeastSquaresEstimator(6, 8, lam, w).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    m2 = PerClassWeightedLeastSquaresEstimator(6, 8, lam, w).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    p1 = np.asarray(m1.apply_batch(Dataset.of(X)).array())
    p2 = np.asarray(m2.apply_batch(Dataset.of(X)).array())
    assert (p1.argmax(1) == y).mean() > 0.95
    assert (p2.argmax(1) == y).mean() > 0.95


def test_per_class_weighted_shuffled_invariance(mesh8):
    """Class-grouping must be order-independent (reference tests shuffled
    CSV fixtures)."""
    X, Y, _ = _weighted_problem(n=60, D=6, C=2, seed=3)
    perm = np.random.default_rng(0).permutation(len(X))
    est = BlockWeightedLeastSquaresEstimator(6, 1, 0.1, 0.5)
    m1 = est.fit(Dataset.of(X), Dataset.of(Y))
    m2 = est.fit(Dataset.of(X[perm]), Dataset.of(Y[perm]))
    np.testing.assert_allclose(
        np.asarray(m1.W), np.asarray(m2.W), atol=1e-3
    )


@pytest.mark.parametrize("num_iter,block_size", [(1, 10), (2, 4)])
def test_block_weighted_pcg_matches_reference_translation(
    mesh8, num_iter, block_size
):
    """The matrix-free PCG solve path (solve="pcg") must reproduce the
    same reference translation the Cholesky path does."""
    X, Y, _ = _weighted_problem()
    lam, w = 0.1, 0.6
    est = BlockWeightedLeastSquaresEstimator(
        block_size, num_iter, lam, w, class_chunk=2, solve="pcg"
    )
    model = est.fit(Dataset.of(X).shard(), Dataset.of(Y).shard())
    W_ref, b_ref = ref_block_weighted_bcd(X, Y, block_size, num_iter, lam, w)
    np.testing.assert_allclose(np.asarray(model.W), W_ref, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(model.intercept), b_ref, atol=2e-2
    )


def test_block_weighted_pcg_agrees_with_chol():
    """pcg and chol are two solvers for the same systems: their fitted
    models must agree far tighter than either's tolerance vs f64."""
    X, Y, _ = _weighted_problem(n=200, D=48, C=4, seed=3)
    kw = dict(block_size=48, num_iter=1, lam=0.05, mixture_weight=0.5)
    chol = BlockWeightedLeastSquaresEstimator(solve="chol", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    pcg = BlockWeightedLeastSquaresEstimator(solve="pcg", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    np.testing.assert_allclose(
        np.asarray(pcg.W), np.asarray(chol.W), atol=5e-4
    )


@pytest.mark.parametrize("solve", ["pcg", "chol"])
def test_block_weighted_fits_blocks_wider_than_the_gram_leaf(
        solve, monkeypatch):
    """Blocks of 640 columns: the population Gram is built from its upper
    block triangle (``block_ls._sym_gram``: 384 | 256, three products)
    and both solvers fit the reference translation's model as before;
    the fit counts the pairs it multiplied and no block-solver Gram."""
    from keystone_tpu.observability import registry
    from keystone_tpu.ops.learning import block_ls

    assert block_ls._gram_cut(640) == 384
    X, Y, _ = _weighted_problem(n=1500, D=1280, C=3, seed=5)
    lam, w, sweeps = 0.1, 0.5, 2
    # a registry of this test's own: the counts are of this fit alone
    monkeypatch.setattr(registry, "_global_registry",
                        registry.MetricsRegistry())
    est = BlockWeightedLeastSquaresEstimator(
        640, sweeps, lam, w, solve=solve, pcg_tol=1e-6)
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    W_ref, b_ref = ref_block_weighted_bcd(X, Y, 640, sweeps, lam, w)
    np.testing.assert_allclose(np.asarray(model.W), W_ref, atol=5e-4)
    np.testing.assert_allclose(np.asarray(model.intercept), b_ref, atol=2e-3)

    def count(name):
        return registry.get_global_registry().counter(
            "keystone_solver_" + name + "_total").get()

    # two blocks, two sweeps: four Grams of 640 columns
    assert count("gram_pairs") == 4 * 640 * 640
    assert count("gram_pairs_computed") == 4 * block_ls._gram_pairs(640)[0]
    assert count("gram_builds") == 0


def test_block_weighted_skewed_classes_gathered_layout(mesh8):
    """Heavy class imbalance on EVERY physical path: the chol solver's
    grouped and (explicitly forced) gathered layouts, and the ungrouped
    PCG solver, all against the f64 reference translation. The r3 test
    relied on the auto layout heuristic tripping 'gathered' but the
    fixture never actually crossed the threshold (ADVICE r3) — the
    ``layout`` override pins each path explicitly."""
    rng = np.random.default_rng(5)
    # counts [84, 3, 2, 1]
    y = np.concatenate([
        np.zeros(84, np.int64), np.full(3, 1), np.full(2, 2), [3],
    ])
    C, D = 4, 10
    centers = rng.standard_normal((C, D)) * 2
    X = (centers[y] + rng.standard_normal((len(y), D))).astype(np.float32)
    Y = (2.0 * np.eye(C, dtype=np.float32)[y] - 1.0)
    lam, w = 0.1, 0.6
    W_ref, b_ref = ref_block_weighted_bcd(X, Y, 10, 1, lam, w)
    cases = [
        dict(solve="chol", layout="grouped"),
        dict(solve="chol", layout="gathered"),
        dict(solve="pcg"),
    ]
    for kw in cases:
        est = BlockWeightedLeastSquaresEstimator(
            10, 1, lam, w, class_chunk=2, **kw
        )
        model = est.fit(Dataset.of(X), Dataset.of(Y))
        np.testing.assert_allclose(
            np.asarray(model.W), W_ref, atol=2e-2, err_msg=str(kw)
        )
        np.testing.assert_allclose(
            np.asarray(model.intercept), b_ref, atol=2e-2, err_msg=str(kw)
        )


def test_block_weighted_layout_memory_budget(monkeypatch):
    """The auto layout decision must refuse the grouped copy when it
    would not fit the device memory budget (ADVICE r3), falling back to
    the gathered path — results unchanged."""
    from keystone_tpu.ops.learning import weighted_ls as wls

    X, Y, _ = _weighted_problem(n=96, D=12, C=3, seed=7)
    est = BlockWeightedLeastSquaresEstimator(12, 1, 0.05, 0.5, solve="chol")
    W_normal = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    gathered_ran = {}
    orig = wls._class_chunk_stats_gathered

    def spy(*a, **k):
        gathered_ran["yes"] = True
        return orig(*a, **k)

    monkeypatch.setattr(wls, "_class_chunk_stats_gathered", spy)
    monkeypatch.setattr(wls, "_device_memory_limit", lambda: 1)
    W_tight = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    assert gathered_ran.get("yes"), "tight budget must force gathered"
    np.testing.assert_allclose(W_tight, W_normal, atol=1e-4)


def test_block_weighted_pcg_reports_convergence():
    X, Y, _ = _weighted_problem(n=120, D=16, C=3, seed=2)
    model = BlockWeightedLeastSquaresEstimator(
        16, 1, 0.05, 0.5, solve="pcg"
    ).fit(Dataset.of(X), Dataset.of(Y))
    rel = float(model.solver_info["pcg_max_rel_residual"])
    assert rel < 1e-5, rel  # converged, and the diagnostic surfaces it
    # chol path attaches no PCG diagnostics
    model2 = BlockWeightedLeastSquaresEstimator(
        16, 1, 0.05, 0.5, solve="chol"
    ).fit(Dataset.of(X), Dataset.of(Y))
    assert model2.solver_info is None


def test_block_weighted_pcg_ragged_blocks_match_chol():
    """D not divisible by block_size: the PCG path takes the per-block
    dispatch fallback (non-uniform widths) instead of the fused scan —
    both must produce the same model as the exact chol solver."""
    X, Y, _ = _weighted_problem(n=160, D=20, C=4, seed=9)
    kw = dict(block_size=8, num_iter=2, lam=0.05, mixture_weight=0.5)
    chol = BlockWeightedLeastSquaresEstimator(solve="chol", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    pcg = BlockWeightedLeastSquaresEstimator(solve="pcg", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    np.testing.assert_allclose(
        np.asarray(pcg.W), np.asarray(chol.W), atol=5e-4
    )
    np.testing.assert_allclose(
        np.asarray(pcg.intercept), np.asarray(chol.intercept), atol=5e-4
    )


def test_limb_splitting_recovers_f32_products():
    """The bf16 limb decomposition behind the PCG GEMMs: a bf16 x
    3-limb contraction must match the f64 reference to ~2^-24
    relative."""
    import jax.numpy as jnp

    from keystone_tpu.ops.learning.weighted_ls import (
        _dot00, _limb3, _sum3,
    )

    rng = np.random.default_rng(0)
    a16 = jnp.asarray(
        rng.standard_normal((512, 64)).astype(np.float32), jnp.bfloat16
    )
    b32 = jnp.asarray(rng.standard_normal((512, 8)).astype(np.float32))
    exact = np.asarray(a16, np.float64).T @ np.asarray(b32, np.float64)
    scale = np.abs(exact).max()

    out3 = np.asarray(_sum3(_dot00(a16, _limb3(b32, 1)), axis=1))
    assert np.abs(out3 - exact).max() / scale < 1e-6

    # and the limbs themselves reconstruct the f32 operand
    limbs = np.asarray(_limb3(b32, 1), np.float64)
    recon = limbs[:, :8] + limbs[:, 8:16] + limbs[:, 16:]
    assert np.abs(recon - np.asarray(b32, np.float64)).max() < 1e-7


def test_block_weighted_multi_hot_rows_agree_across_solvers():
    """ADVICE r4: multi-hot ±1 indicator rows must land in exactly ONE
    class — the argmax/first-positive (identical for indicators) — in
    BOTH solver paths, so pcg and chol fit the same systems."""
    X, Y, _ = _weighted_problem(n=200, D=48, C=4, seed=5)
    Y = np.asarray(Y).copy()
    # make a third of the rows multi-hot: add a second +1 at a LATER
    # column than the original positive (argmax keeps the first)
    rng = np.random.default_rng(0)
    for i in rng.choice(200, 66, replace=False):
        c = int(np.argmax(Y[i]))
        if c < 3:
            Y[i, c + 1 :][rng.integers(0, 4 - c - 1)] = 1.0
    kw = dict(block_size=48, num_iter=1, lam=0.05, mixture_weight=0.5)
    chol = BlockWeightedLeastSquaresEstimator(solve="chol", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    pcg = BlockWeightedLeastSquaresEstimator(solve="pcg", **kw).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    np.testing.assert_allclose(
        np.asarray(pcg.W), np.asarray(chol.W), atol=5e-4
    )


# -- the CG matvec on class-sorted rows (PR 30) ---------------------------


def _sorted_fits_total():
    from keystone_tpu.observability.registry import get_global_registry

    return get_global_registry().counter(
        "keystone_solver_wls_sorted_fits_total"
    ).get()


def _path_total(layout):
    from keystone_tpu.observability.registry import get_global_registry

    return get_global_registry().counter(
        "keystone_solver_wls_path_total", labelnames=("solve", "layout")
    ).get(("pcg", layout))


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 16 rows and windows of 4 classes, so that fits of a
    hundred rows have several tiles, pad rows and several windows."""
    from keystone_tpu.ops.learning import weighted_ls as wls

    monkeypatch.setattr(wls, "_SORT_TILE", 16)
    monkeypatch.setattr(wls, "_SORT_WINDOW", 4)
    return wls


def _matvec_case(case):
    """(X (n, b), class of each row or -1 for none, C, tile, window)."""
    rng = np.random.default_rng(11)
    n, b, C, tile, window = 96, 12, 6, 16, 4
    dtype = np.float32
    if case == "many_windows":
        n, C = 192, 12
    elif case == "pad_rows":
        n = 90  # 5 tiles of 16 and 10 rows of a sixth
    y = rng.integers(0, C, n)
    if case == "sorted_already":
        y = np.sort(y)
    elif case == "empty_class":
        y[y == 2] = 3
    elif case == "no_class_rows":
        y[rng.choice(n, 20, replace=False)] = -1
    elif case == "one_tile":
        tile = 96
        y = rng.integers(0, 3, n)  # 96 rows in 3 classes: one window
    X = rng.standard_normal((n, b)).astype(dtype)
    return X, y, C, tile, window


@pytest.mark.parametrize("case", [
    "shuffled", "sorted_already", "empty_class", "pad_rows",
    "no_class_rows", "many_windows", "one_tile", "bf16",
])
def test_sorted_rows_products_match_the_one_hot_products(case):
    """z_i = x_i·v_{y_i} and Σ_{i in c} x_i z_i on class-sorted tiles
    against the same two products over all classes in float64."""
    import jax.numpy as jnp

    from keystone_tpu.ops.learning import weighted_ls as wls

    X, y, C, tile, window = _matvec_case(case)
    if case == "bf16":
        Xd = jnp.asarray(X, jnp.bfloat16)
        X = np.asarray(Xd.astype(jnp.float32))
    else:
        Xd = jnp.asarray(X)
    P = np.zeros((len(y), C), np.float32)
    P[np.flatnonzero(y >= 0), y[y >= 0]] = 1.0
    order, kcls = wls._class_sorted_rows(jnp.asarray(P, jnp.bfloat16), tile)
    assert order.shape[1] == tile and order.shape == kcls.shape
    # sorted by class, rows of no class and pad rows last
    k = np.asarray(kcls).ravel()
    assert np.all(np.diff(k) >= 0)
    labelled = int((y >= 0).sum())
    np.testing.assert_array_equal(
        k[:labelled], np.sort(y[y >= 0]))
    assert np.all(k[labelled:] == C)
    products = wls._sorted_class_products(Xd[order], kcls, C, window)
    v = np.random.default_rng(1).standard_normal((C, X.shape[1])).astype(
        np.float32)
    got = np.asarray(products(jnp.asarray(v)))
    X64, v64 = X.astype(np.float64), v.astype(np.float64)
    z = np.einsum("nb,ncb->nc", X64, v64[None].repeat(len(y), 0))
    z = (z * P).sum(1)
    want = (P * z[:, None]).T @ X64
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", [
    "shuffled", "sorted_already", "empty_class", "pad_rows",
    "no_class_rows", "many_windows", "one_tile", "bf16", "multi_hot",
])
def test_sorted_rows_moments_match_the_one_hot_moments(case):
    """The statistics' class sums PᵀX, own-residual sums Xᵀ(P ⊙ r) and
    Pᵀr on class-sorted tiles, with r each row's residual in its own
    class taken by index, against the one-hot forms over all classes in
    float64 (P: each row's first positive label, as the fit takes it)."""
    import jax.numpy as jnp

    from keystone_tpu.ops.learning import weighted_ls as wls

    X, y, C, tile, window = _matvec_case(
        "shuffled" if case == "multi_hot" else case)
    if case == "bf16":
        Xd = jnp.asarray(X, jnp.bfloat16)
        X = np.asarray(Xd.astype(jnp.float32))
    else:
        Xd = jnp.asarray(X)
    n = len(y)
    Y = np.full((n, C), -1.0, np.float32)
    Y[np.flatnonzero(y >= 0), y[y >= 0]] = 1.0
    if case == "multi_hot":  # a later +1 on a third of the rows
        for i in np.random.default_rng(0).choice(n, n // 3, replace=False):
            if y[i] < C - 1:
                Y[i, y[i] + 1:][0] = 1.0
    P, _ = wls._membership(jnp.asarray(Y), jnp.ones((n,), jnp.float32))
    order, kcls = wls._class_sorted_rows(P, tile)
    R = np.random.default_rng(3).standard_normal((n, C)).astype(np.float32)
    r = wls._own_class_entries(jnp.asarray(R), order, kcls)
    sums, rsums, rtot = (np.asarray(a) for a in wls._sorted_class_moments(
        Xd[order], kcls, r, C, window))
    P64 = np.asarray(P, np.float64)
    X64 = X.astype(np.float64)
    r64 = (P64 * R).sum(1)
    np.testing.assert_allclose(sums, P64.T @ X64, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        rsums, (P64 * r64[:, None]).T @ X64, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(rtot, P64.T @ r64, rtol=2e-5, atol=2e-5)


def _products_outside_loops(fn, *args):
    """The operand shapes of every product in ``fn``'s program outside
    its loops (nested calls followed, ``while`` bodies not)."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                continue
            if eqn.primitive.name == "dot_general":
                found.append(tuple(tuple(v.aval.shape) for v in eqn.invars))
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", p)
                if hasattr(sub, "eqns"):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("sorted_rows", [True, False])
def test_statistics_meet_every_class_in_x_t_r_alone_on_sorted_rows(
        sorted_rows):
    """On class-sorted rows the one product of a block step's statistics
    that meets every row's features with all C classes is XᵀR, dense in
    R (with the labelled rows' column sums as one more column): the
    class sums and the own-residual sums read the sorted copy against
    windows of classes. On the original rows the one-hot products stay
    as they were: XᵀR, PᵀX and Xᵀ(P ⊙ r)."""
    from functools import partial

    import jax.numpy as jnp

    from keystone_tpu.ops.learning import weighted_ls as wls

    n, b, C, tile, window = 96, 12, 6, 16, 4
    X, Y, _ = _weighted_problem(n=n, D=b, C=C, seed=4)
    mask = jnp.ones((n,), jnp.float32)
    P, _, inv_counts, valid, _, R, sort = wls._pcg_setup_core(
        jnp.asarray(Y), mask, 0.5, n, tile if sorted_rows else 0)
    step = partial(wls._pcg_block_core, width=b, n=n,
                   sort_window=window if sorted_rows else 0)
    products = _products_outside_loops(
        step, jnp.asarray(X), R, P, jnp.zeros((b, C)), inv_counts, valid,
        0, 0.5, 0.05, sort)
    every_class = sorted(p for p in products if (n, b) in p and any(
        s[0] == n and s[1] >= C for s in p if s != (n, b)))
    if sorted_rows:
        assert every_class == [((n, b), (n, C + 1))], products  # Xᵀ[R | 1]
    else:
        assert every_class == [((n, C), (n, b))] + [((n, b), (n, C))] * 2


@pytest.mark.parametrize("counts,tile,window,want", [
    # 1,000 classes of 235 to 420 rows: a 16,384-row tile meets 72
    ([235 + (i * 37) % 186 for i in range(1000)], 16384, 128, True),
    # classes of ~20 rows: 16,384 rows would meet ~800
    ([20] * 4000, 16384, 128, False),
    # classes of 128 rows fill a window exactly; of 127, one more slips in
    ([128] * 1000, 16384, 128, True),
    ([127] * 1000, 16384, 128, False),
    # the empty classes between two classes count: the window is
    # contiguous in the class index
    ([8, 0, 0, 0, 0, 8], 16, 4, False),
    ([8, 0, 0, 8, 0, 0], 16, 4, True),
    # tiles start at multiples of the tile: 4 classes of 4 fill one, a
    # first class of 2 shifts a fifth class into it
    ([4, 4, 4, 4, 4], 16, 4, True),
    ([2, 4, 4, 4, 4], 16, 4, False),
    ([2, 4, 4, 4, 4], 8, 4, True),
    # no row has a class
    ([0, 0, 0], 16, 4, False),
])
def test_tiles_fit_window_follows_the_class_counts(
        monkeypatch, counts, tile, window, want):
    from keystone_tpu.ops.learning import weighted_ls as wls

    monkeypatch.setattr(wls, "_SORT_WINDOW", window)
    assert wls._tiles_fit_window(np.asarray(counts), tile) is want


@pytest.mark.parametrize("n,width,steps,limit,want", [
    # a fit smaller than the tile is one tile, rounded up to 8 rows
    (90, 64, 1, 1 << 30, 96),
    # 2,048 rows of 64 float32 features, 8 classes: X, Y, the copy and
    # two (2048, 128) products are 3,211,264 bytes; across block steps
    # the residual twice and the membership too (2.5 Y): 3,375,104;
    # a block of 32 columns is half the copy, and as much again for
    # its cut out of X: 3,375,104 too (3,112,960 without the cut)
    (2048, 64, 1, 3_600_000, 2048),
    (2048, 64, 2, 3_600_000, 0),
    (2048, 64, 2, 3_800_000, 2048),
    (2048, 64, 1, 3_500_000, 0),
    (2048, 32, 4, 3_800_000, 2048),
    (2048, 32, 4, 3_600_000, 0),
    # the flagship's fit on a v5e (15.75 GiB): 12.38 GB of 15.22; a
    # fifth more rows still fit, a quarter more do not; nor does a
    # second pass over the block (15.66 GB), while two blocks of 2,048
    # columns do on a seventh fewer rows
    (327680, 4096, 1, 15.75 * 2**30, 16384),
    (393216, 4096, 1, 15.75 * 2**30, 16384),
    (425984, 4096, 1, 15.75 * 2**30, 0),
    (327680, 4096, 2, 15.75 * 2**30, 0),
    (278528, 2048, 2, 15.75 * 2**30, 16384),
])
def test_sorted_layout_follows_the_bytes_the_program_holds(
        monkeypatch, n, width, steps, limit, want):
    """The budget is X + Y + the block's copy + two tile products (and,
    across block steps, 2.5 Y and a narrower block's cut) against nine
    tenths of the device's memory."""
    import types

    from keystone_tpu.ops.learning import weighted_ls as wls

    flagship = n > 4096
    b, C = (4096, 1000) if flagship else (64, 8)

    def shaped(rows, cols):  # what the budget reads of an array
        return types.SimpleNamespace(
            shape=(rows, cols), nbytes=rows * cols * 4,
            dtype=np.dtype(np.float32))

    monkeypatch.setattr(wls, "_device_memory_limit", lambda: limit)
    monkeypatch.setattr(
        wls, "_class_counts", lambda Y, mask: (np.full(C, n // C), True))
    assert wls._sorted_layout(
        shaped(n, b), shaped(n, C), None, width, steps) == (want, want > 0)


def _sorted_fit_case(case):
    """(X, Y, estimator keywords, whether the loop translation applies)."""
    kw = dict(block_size=48, num_iter=1, lam=0.05, mixture_weight=0.5)
    X, Y, y = _weighted_problem(n=200, D=48, C=6, seed=13)
    translates = True
    if case == "sorted_labels":
        o = np.argsort(y, kind="stable")
        X, Y = X[o], Y[o]
    elif case == "empty_class":
        Y = Y.copy()
        rows = np.flatnonzero(y == 2)
        Y[rows, 2], Y[rows, 3] = -1.0, 1.0
        translates = False  # the translation divides by a class's count
    elif case == "pad_rows":
        X, Y = X[:187], Y[:187]
    elif case == "multi_hot":
        Y = Y.copy()
        for i in np.random.default_rng(0).choice(200, 66, replace=False):
            c = int(np.argmax(Y[i]))
            if c < 5:
                Y[i, c + 1] = 1.0  # a later +1: the first positive wins
    elif case == "ragged_tail":
        kw.update(block_size=20, num_iter=2)  # widths 20, 20, 8
    elif case == "two_blocks":
        kw.update(block_size=24, num_iter=2)  # the fused scan, 4 steps
    else:
        assert case == "shuffled", case
    return X, Y, kw, translates


@pytest.mark.parametrize("case", [
    "shuffled", "sorted_labels", "empty_class", "pad_rows", "multi_hot",
    "ragged_tail", "two_blocks",
])
def test_pcg_fit_on_sorted_rows_matches_chol_and_the_translation(
        small_tiles, case):
    X, Y, kw, translates = _sorted_fit_case(case)
    before = _sorted_fits_total()
    pcg = BlockWeightedLeastSquaresEstimator(solve="pcg", **kw).fit(
        Dataset.of(X), Dataset.of(Y))
    assert _sorted_fits_total() == before + 1, "the sorted rows did not run"
    chol = BlockWeightedLeastSquaresEstimator(solve="chol", **kw).fit(
        Dataset.of(X), Dataset.of(Y))
    np.testing.assert_allclose(
        np.asarray(pcg.W), np.asarray(chol.W), atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(pcg.intercept), np.asarray(chol.intercept), atol=5e-4)
    assert float(pcg.solver_info["pcg_max_rel_residual"]) <= 1e-5
    if translates:
        W_ref, b_ref = ref_block_weighted_bcd(
            X, Y, kw["block_size"], kw["num_iter"], kw["lam"],
            kw["mixture_weight"])
        np.testing.assert_allclose(np.asarray(pcg.W), W_ref, atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(pcg.intercept), b_ref, atol=2e-2)


def test_pcg_fit_on_sorted_bf16_rows_matches_the_f32_fit_of_the_same_rows(
        small_tiles):
    """bf16 features take the limb products on sorted rows too: the
    model is the float32 fit's of the same (bf16-exact) features."""
    import jax.numpy as jnp

    X, Y, _ = _weighted_problem(n=200, D=48, C=6, seed=13)
    X16 = jnp.asarray(X, jnp.bfloat16)
    kw = dict(block_size=48, num_iter=1, lam=0.05, mixture_weight=0.5,
              solve="pcg")
    before = _sorted_fits_total()
    m16 = BlockWeightedLeastSquaresEstimator(**kw).fit(
        Dataset.from_array(X16), Dataset.of(Y))
    m32 = BlockWeightedLeastSquaresEstimator(**kw).fit(
        Dataset.of(np.asarray(X16.astype(jnp.float32))), Dataset.of(Y))
    assert _sorted_fits_total() == before + 2
    np.testing.assert_allclose(
        np.asarray(m16.W), np.asarray(m32.W), atol=5e-5)


def _no_room(monkeypatch, wls):
    monkeypatch.setattr(wls, "_device_memory_limit", lambda: 1)


def _window_too_narrow(monkeypatch, wls):
    # 6 classes of ~33 rows, and a tile of 64 rows meets three of them
    monkeypatch.setattr(wls, "_SORT_TILE", 64)
    monkeypatch.setattr(wls, "_SORT_WINDOW", 2)


@pytest.mark.parametrize("why", ["no_room", "window_too_narrow", "sharded"])
def test_pcg_falls_back_to_the_original_rows_and_fits_the_same_model(
        monkeypatch, small_tiles, mesh8, why):
    """Where the copy does not fit, the class counts break the window
    bound or the rows are spread over devices, the one-hot matvec runs
    as before, the counters say so, and the model is the same."""
    from keystone_tpu.parallel import mesh as mesh_lib

    wls = small_tiles
    X, Y, _ = _weighted_problem(n=200, D=48, C=6, seed=13)
    kw = dict(block_size=48, num_iter=1, lam=0.05, mixture_weight=0.5)
    est = BlockWeightedLeastSquaresEstimator(**kw, solve="pcg")
    one = mesh_lib.make_mesh(n_data=1, devices=mesh8.devices.ravel()[:1])
    with mesh_lib.use_mesh(one):
        s0, p0 = _sorted_fits_total(), _path_total("sorted")
        W_sorted = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
        assert (_sorted_fits_total(), _path_total("sorted")) == (
            s0 + 1, p0 + 1)
    s0, o0 = _sorted_fits_total(), _path_total("original")
    if why == "sharded":
        model = est.fit(Dataset.of(X).shard(), Dataset.of(Y).shard())
    else:
        {"no_room": _no_room, "window_too_narrow": _window_too_narrow}[why](
            monkeypatch, wls)
        with mesh_lib.use_mesh(one):
            model = est.fit(Dataset.of(X), Dataset.of(Y))
    assert (_sorted_fits_total(), _path_total("original")) == (s0, o0 + 1)
    np.testing.assert_allclose(np.asarray(model.W), W_sorted, atol=1e-4)


def test_sorted_fits_share_metric_reads_the_program_s_counters(
        monkeypatch, small_tiles):
    """The benchmark's ``wls_sorted_fits_share.wfit`` is one data file:
    both families it names exist after one small pcg fit, and its
    reader gives 1.0 after sorted fits and less after a fall-back."""
    import json
    import os

    from benchmark.readers import counter_ratio
    from keystone_tpu.observability import registry

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "metrics",
                           "wls_sorted_fits_share.wfit.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counter_ratio"
    entry = [m for m in json.load(open(os.path.join(root, "BENCHMARK.json")))
             ["per_layer"] if m["name"] == "wls_sorted_fits_share.wfit"]
    assert entry == [{
        "name": "wls_sorted_fits_share.wfit", "unit": "share",
        "better": "higher", "source": "program_counter",
        "layer": "Solvers", "moves": "fit_rows_per_s",
        "workloads": ["weighted-bcd-fit"]}]
    # a registry of this test's own: the ratio counts since it began
    monkeypatch.setattr(registry, "_global_registry",
                        registry.MetricsRegistry())
    assert counter_ratio.read(None, **metric["args"]) is None  # no fit yet
    X, Y, _ = _weighted_problem(n=120, D=16, C=3, seed=2)
    est = BlockWeightedLeastSquaresEstimator(16, 1, 0.05, 0.5, solve="pcg")
    est.fit(Dataset.of(X), Dataset.of(Y))
    names = {f.name for f in registry.get_global_registry().collect()}
    assert {metric["args"]["numerator"],
            metric["args"]["denominator"]} <= names
    assert counter_ratio.read(None, **metric["args"]) == 1.0
    _no_room(monkeypatch, small_tiles)
    est.fit(Dataset.of(X), Dataset.of(Y))
    assert counter_ratio.read(None, **metric["args"]) == 0.5


@pytest.mark.parametrize("layout", ["sorted", "original"])
def test_sorted_stats_share_metric_reads_the_program_s_counters(
        monkeypatch, small_tiles, layout):
    """The benchmark's ``wls_sorted_stats_share.wfit`` is one data file:
    its reader gives 1.0 after a fit whose statistics read class-sorted
    rows and 0.0 after a fit on the original rows."""
    import json
    import os

    from benchmark.readers import counter_ratio
    from keystone_tpu.observability import registry

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "metrics",
                           "wls_sorted_stats_share.wfit.json")) as f:
        metric = json.load(f)
    assert metric == {"reader": "counter_ratio", "args": {
        "numerator": "keystone_solver_wls_sorted_stats_fits_total",
        "denominator": "keystone_solver_wls_fits_total"}}
    entry = [m for m in json.load(open(os.path.join(root, "BENCHMARK.json")))
             ["per_layer"] if m["name"] == "wls_sorted_stats_share.wfit"]
    assert entry == [{
        "name": "wls_sorted_stats_share.wfit", "unit": "share",
        "better": "higher", "source": "program_counter",
        "layer": "Solvers", "moves": "fit_rows_per_s",
        "workloads": ["weighted-bcd-fit"]}]
    monkeypatch.setattr(registry, "_global_registry",
                        registry.MetricsRegistry())
    if layout == "original":
        _no_room(monkeypatch, small_tiles)
    X, Y, _ = _weighted_problem(n=120, D=16, C=3, seed=2)
    BlockWeightedLeastSquaresEstimator(16, 1, 0.05, 0.5, solve="pcg").fit(
        Dataset.of(X), Dataset.of(Y))
    assert _path_total(layout) == 1
    assert counter_ratio.read(None, **metric["args"]) == (
        1.0 if layout == "sorted" else 0.0)


# -- a first block step's moments from the class sums (PR 41) ------------


def _label_moments_fits_total():
    from keystone_tpu.observability.registry import get_global_registry

    return get_global_registry().counter(
        "keystone_solver_wls_label_moments_fits_total"
    ).get()


def _dense_moments_fit(monkeypatch, wls, est, X, Y):
    """The same fit with the labels' test forced false: every block
    step's statistics take the products with R, as the parent's did."""
    counts_and_test = wls._class_counts
    with monkeypatch.context() as m:
        m.setattr(wls, "_class_counts",
                  lambda Y, mask: (counts_and_test(Y, mask)[0], False))
        return est.fit(Dataset.of(X), Dataset.of(Y))


@pytest.mark.parametrize("layout", ["sorted", "original"])
@pytest.mark.parametrize("blocks", [
    "one_block", "two_blocks", "one_block_twice", "ragged_tail"])
def test_first_step_moments_from_class_sums_fit_the_dense_model(
        monkeypatch, small_tiles, layout, blocks):
    """Single-label ±1 indicators: on sorted rows the first block step
    derives XᵀR, Xᵀ(P ⊙ r) and R's means from the class sums and counts,
    and the model is the dense moments' to float32 rounding; the counter
    rises by one a fit there and not on the original rows."""
    wls = small_tiles
    X, Y, _ = _weighted_problem(n=190, D=16, C=6, seed=2)
    block_size, num_iter = {
        "one_block": (16, 1), "two_blocks": (8, 1),
        "one_block_twice": (16, 2), "ragged_tail": (6, 1),  # 6, 6, 4
    }[blocks]
    if layout == "original":
        _no_room(monkeypatch, wls)
    est = BlockWeightedLeastSquaresEstimator(
        block_size, num_iter, 0.1, 0.5, solve="pcg", pcg_tol=1e-7,
        convergence_check="off")
    before = _label_moments_fits_total()
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    assert _path_total(layout) >= 1
    assert _label_moments_fits_total() == before + (layout == "sorted")
    dense = _dense_moments_fit(monkeypatch, wls, est, X, Y)
    for got, want in ((model.W, dense.W),
                      (model.intercept, dense.intercept)):
        want = np.asarray(want)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=0,
            atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("labels", ["multi_hot", "no_positive", "real"])
def test_labels_other_than_single_indicators_take_the_dense_moments(
        monkeypatch, small_tiles, labels):
    """A multi-hot row, a row with no positive entry, real-valued Y:
    the first block step keeps the products with R, bit for bit."""
    wls = small_tiles
    X, Y, _ = _weighted_problem(n=190, D=16, C=6, seed=2)
    Y = Y.copy()
    if labels == "multi_hot":
        Y[7, (int(np.argmax(Y[7])) + 1) % 6] = 1.0
    elif labels == "no_positive":
        Y[7] = -1.0
    else:
        Y = Y * np.float32(0.75) + np.float32(0.01) * np.random.default_rng(
            0).standard_normal(Y.shape).astype(np.float32)
    est = BlockWeightedLeastSquaresEstimator(16, 1, 0.1, 0.5, solve="pcg")
    before = _label_moments_fits_total()
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    assert _label_moments_fits_total() == before
    assert _path_total("sorted") >= 1
    dense = _dense_moments_fit(monkeypatch, wls, est, X, Y)
    np.testing.assert_array_equal(np.asarray(model.W), np.asarray(dense.W))
    np.testing.assert_array_equal(
        np.asarray(model.intercept), np.asarray(dense.intercept))


@pytest.mark.parametrize("case,want", [
    ("indicators", True), ("masked_rows_hold_anything", True),
    ("multi_hot", False), ("no_positive", False), ("real", False),
    ("half_masked_row", False),
])
def test_class_counts_tell_single_label_indicators(case, want):
    import jax.numpy as jnp

    from keystone_tpu.ops.learning import weighted_ls as wls

    _, Y, y = _weighted_problem(n=40, D=4, C=5, seed=1)
    mask = np.ones(40, np.float32)
    if case == "masked_rows_hold_anything":
        mask[-6:] = 0.0
        Y[-6:-3], Y[-3:] = 0.0, 3.5
    elif case == "multi_hot":
        Y[3] = 1.0
    elif case == "no_positive":
        Y[3] = -1.0
    elif case == "real":
        Y[3, y[3]] = 0.9
    elif case == "half_masked_row":
        mask[3] = 0.5
    counts, single = wls._class_counts(jnp.asarray(Y), jnp.asarray(mask))
    assert bool(single) is want
    np.testing.assert_array_equal(
        np.asarray(counts), np.asarray(wls._membership(
            jnp.asarray(Y), jnp.asarray(mask))[1]))


def test_first_step_from_indicator_labels_meets_no_class_with_every_row():
    """With ``labels`` the first block step's statistics make no product
    of the rows with all C classes (XᵀR is derived) and one windowed
    product on the sorted copy (the class sums) where the dense step
    makes two."""
    from functools import partial

    import jax.numpy as jnp

    from keystone_tpu.ops.learning import weighted_ls as wls

    n, b, C, tile, window = 96, 12, 6, 16, 4
    X, Y, _ = _weighted_problem(n=n, D=b, C=C, seed=4)
    mask = jnp.ones((n,), jnp.float32)
    P, counts, inv_counts, valid, jlm, R, sort = wls._pcg_setup_core(
        jnp.asarray(Y), mask, 0.5, n, tile)
    step = partial(wls._pcg_block_core, width=b, n=n, sort_window=window)
    args = (jnp.asarray(X), R, P, jnp.zeros((b, C)), inv_counts, valid,
            0, 0.5, 0.05, sort)

    def shapes(labels):
        products = _products_outside_loops(step, *args, labels)
        every_class = [p for p in products if (n, b) in p and any(
            s[0] == n and s[1] >= C for s in p if s != (n, b))]
        windowed = [p for p in products if (n // tile, tile, b) in p]
        return every_class, windowed

    every_class, windowed = shapes((counts, jlm, mask))
    assert every_class == [] and [sorted(p) for p in windowed] == [
        [(n // tile, tile, window), (n // tile, tile, b)]], windowed
    every_class, windowed = shapes(None)
    assert len(every_class) == 1 and len(windowed) == 2


@pytest.mark.parametrize("layout", ["sorted", "original"])
def test_label_moments_share_metric_reads_the_program_s_counters(
        monkeypatch, small_tiles, layout):
    """The benchmark's ``wls_label_moments_share.wfit`` is one data file:
    its reader gives 1.0 after a sorted fit of single-label indicators
    and 0.0 after a fit on the original rows."""
    import json
    import os

    from benchmark.readers import counter_ratio
    from keystone_tpu.observability import registry

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "metrics",
                           "wls_label_moments_share.wfit.json")) as f:
        metric = json.load(f)
    assert metric == {"reader": "counter_ratio", "args": {
        "numerator": "keystone_solver_wls_label_moments_fits_total",
        "denominator": "keystone_solver_wls_fits_total"}}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "wls_label_moments_share.wfit"]
    assert entry == [{
        "name": "wls_label_moments_share.wfit", "unit": "share",
        "better": "higher", "source": "program_counter",
        "layer": "Solvers", "moves": "fit_rows_per_s",
        "workloads": ["weighted-bcd-fit"]}]
    monkeypatch.setattr(registry, "_global_registry",
                        registry.MetricsRegistry())
    if layout == "original":
        _no_room(monkeypatch, small_tiles)
    X, Y, _ = _weighted_problem(n=120, D=16, C=3, seed=2)
    BlockWeightedLeastSquaresEstimator(16, 1, 0.05, 0.5, solve="pcg").fit(
        Dataset.of(X), Dataset.of(Y))
    assert _path_total(layout) == 1
    assert counter_ratio.read(None, **metric["args"]) == (
        1.0 if layout == "sorted" else 0.0)
