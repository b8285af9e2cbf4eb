"""Solver tests (reference suites: LinearMapperSuite,
BlockLinearMapperSuite — distributed solutions vs local closed form)."""

import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.ops.learning import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
    LocalLeastSquaresEstimator,
)
from keystone_tpu.parallel.dataset import Dataset


def _ols(A, b, lam=0.0):
    d = A.shape[1]
    return np.linalg.solve(A.T @ A + lam * np.eye(d), A.T @ b)


def test_linear_map_estimator_exact(mesh8):
    rng = np.random.default_rng(0)
    A = rng.standard_normal((64, 8)).astype(np.float32)
    W_true = rng.standard_normal((8, 3)).astype(np.float32)
    b = A @ W_true
    model = LinearMapEstimator().fit(
        Dataset.of(A).shard(), Dataset.of(b).shard()
    )
    np.testing.assert_allclose(np.asarray(model.W), W_true, atol=1e-3)
    out = np.asarray(model.apply_batch(Dataset.of(A)).array())
    np.testing.assert_allclose(out, b, atol=1e-2)


def test_linear_map_estimator_l2(mesh8):
    rng = np.random.default_rng(1)
    A = rng.standard_normal((50, 6)).astype(np.float32)
    b = rng.standard_normal((50, 2)).astype(np.float32)
    lam = 0.7
    model = LinearMapEstimator(lam=lam).fit(Dataset.of(A), Dataset.of(b))
    np.testing.assert_allclose(np.asarray(model.W), _ols(A, b, lam), atol=2e-3)


def test_local_least_squares_d_gg_n():
    rng = np.random.default_rng(2)
    A = rng.standard_normal((20, 100)).astype(np.float32)
    b = rng.standard_normal((20, 4)).astype(np.float32)
    model = LocalLeastSquaresEstimator(lam=0.1).fit(Dataset.of(A), Dataset.of(b))
    n = 20
    K = A @ A.T + 0.1 * n * np.eye(n)
    expect = A.T @ np.linalg.solve(K, b)
    np.testing.assert_allclose(np.asarray(model.W), expect, atol=2e-3)


def test_block_ls_single_block_matches_exact(mesh8):
    """With one block and no padding issues, one BCD sweep = exact
    regularized OLS on centered data."""
    rng = np.random.default_rng(3)
    A = rng.standard_normal((64, 8)).astype(np.float32)
    W_true = rng.standard_normal((8, 3)).astype(np.float32)
    b = A @ W_true + 0.5
    est = BlockLeastSquaresEstimator(block_size=8, num_iter=1, lam=0.0)
    model = est.fit(Dataset.of(A).shard(), Dataset.of(b).shard())
    Ac = A - A.mean(0)
    bc = b - b.mean(0)
    expect = _ols(Ac, bc)
    np.testing.assert_allclose(np.asarray(model.W), expect, atol=5e-3)
    pred = np.asarray(model.apply_batch(Dataset.of(A)).array())
    np.testing.assert_allclose(pred, b, atol=5e-2)


def test_block_ls_converges_to_exact_with_iters(mesh8):
    """Multi-block BCD approaches the exact solution as sweeps increase."""
    rng = np.random.default_rng(4)
    A = rng.standard_normal((128, 12)).astype(np.float32)
    W_true = rng.standard_normal((12, 2)).astype(np.float32)
    b = A @ W_true
    lam = 1e-3
    Ac = A - A.mean(0)
    bc = b - b.mean(0)
    exact = _ols(Ac, bc, lam)

    err1 = _fit_err(A, b, lam, num_iter=1, exact=exact)
    err10 = _fit_err(A, b, lam, num_iter=10, exact=exact)
    assert err10 < err1 or err10 < 1e-3
    assert err10 < 1e-2


def _fit_err(A, b, lam, num_iter, exact):
    est = BlockLeastSquaresEstimator(block_size=5, num_iter=num_iter, lam=lam)
    model = est.fit(Dataset.of(A), Dataset.of(b))
    return float(np.abs(np.asarray(model.W) - exact).max())


def test_block_ls_padding_exact(mesh8):
    """Padded rows (n not a multiple of shard count) must not change the
    solution."""
    rng = np.random.default_rng(5)
    A = rng.standard_normal((61, 6)).astype(np.float32)
    b = rng.standard_normal((61, 2)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=6, num_iter=1, lam=0.1)
    m_sharded = est.fit(Dataset.of(A).shard(), Dataset.of(b).shard())
    m_plain = est.fit(Dataset.of(A), Dataset.of(b))
    np.testing.assert_allclose(
        np.asarray(m_sharded.W), np.asarray(m_plain.W), atol=1e-4
    )


def test_block_linear_mapper_apply_and_evaluate(mesh8):
    rng = np.random.default_rng(6)
    A = rng.standard_normal((32, 10)).astype(np.float32)
    b = rng.standard_normal((32, 3)).astype(np.float32)
    est = BlockLeastSquaresEstimator(block_size=4, num_iter=2, lam=0.01)
    model = est.fit(Dataset.of(A), Dataset.of(b))
    seen = []
    model.apply_and_evaluate(Dataset.of(A), lambda out: seen.append(out))
    assert len(seen) == 3  # ceil(10/4) blocks
    final = np.asarray(model.apply_batch(Dataset.of(A)).array())
    np.testing.assert_allclose(np.asarray(seen[-1])[:32], final, atol=1e-4)


def test_block_ls_weight():
    assert BlockLeastSquaresEstimator(10, num_iter=3).weight == 10


# -- solve="host": the per-fit factor bank --------------------------------
#
# With more than one sweep a fit builds, reads back and factors each
# block's Gram once and solves later visits against the kept float64
# factor. The references: a plain float64 numpy BCD, and the per-step path
# composed from the same programs (Gram rebuilt and refactored each step).


@pytest.fixture
def solver_counters():
    from keystone_tpu.observability.registry import (
        get_global_registry,
        reset_global_registry,
    )

    reset_global_registry()
    yield lambda name: get_global_registry().counter(
        "keystone_solver_" + name + "_total"
    ).get()
    reset_global_registry()


def _bcd_f64(X, Y, bs, num_iter, lam):
    """Centred Gauss-Seidel BCD in float64, every system solved anew."""
    X = np.asarray(X, np.float64)
    Y = np.asarray(Y, np.float64)
    Xc = X - X.mean(0)
    R = Y - Y.mean(0)
    W = np.zeros((X.shape[1], Y.shape[1]))
    for _ in range(num_iter):
        for s in range(0, X.shape[1], bs):
            Xb = Xc[:, s:s + bs]
            R = R + Xb @ W[s:s + bs]
            G = Xb.T @ Xb + lam * np.eye(Xb.shape[1])
            W[s:s + bs] = np.linalg.solve(G, Xb.T @ R)
            R = R - Xb @ W[s:s + bs]
    return W


def _per_step_host_fit(X, Y, bs, num_iter, lam):
    """The host path as it was before the bank, in series: the block's
    Gram and right-hand side, then a whole ``psd_solve_host`` (read back,
    factor, solve) in every step, with no Gram built ahead."""
    from keystone_tpu.ops.learning import block_ls
    from keystone_tpu.ops.learning.hostsolve import psd_solve_host

    data = Dataset.of(X).to_array_mode()
    Xp, n, mask = data.padded(), data.n, data.mask()
    mu, _, R = block_ls._prep(
        Xp, Dataset.of(Y).to_array_mode().padded(), mask, n
    )
    starts = range(0, X.shape[1], bs)
    Wb = {
        s: jnp.zeros((min(bs, X.shape[1] - s), Y.shape[1]), jnp.float32)
        for s in starts
    }
    for _ in range(num_iter):
        for s in starts:
            w = Wb[s].shape[0]
            gram = block_ls._block_stats_gram(Xp, mu, s, width=w, n=n)
            rhs, R_plus = block_ls._block_stats_rhs(
                Xp, R, Wb[s], mu, mask, s, width=w
            )
            Wb[s] = jnp.asarray(psd_solve_host(gram, rhs, lam))
            R = block_ls._residual_update(
                Xp, R_plus, Wb[s], mu, mask, s, width=w
            )
    return np.concatenate([np.asarray(Wb[s]) for s in starts])


def _bank_problem(seed, n=96, d=12, k=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32) + 0.5
    Y = (
        X @ rng.standard_normal((d, k)) + 0.1 * rng.standard_normal((n, k))
    ).astype(np.float32)
    return X, Y


@pytest.mark.parametrize("lam", [0.0, 0.3])
def test_block_ls_host_bank_matches_f64_bcd_and_per_step_path(
    lam, solver_counters
):
    X, Y = _bank_problem(10)
    est = BlockLeastSquaresEstimator(4, num_iter=3, lam=lam, solve="host")
    W = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    # 3 blocks x 3 sweeps: a Gram per block, then the kept factor; the
    # Grams of blocks 1 and 2 were built ahead, under the previous
    # block's factorisation
    assert solver_counters("gram_builds") == 3
    assert solver_counters("gram_prefetches") == 2
    assert solver_counters("factor_reuses") == 6
    assert solver_counters("host_solves") == 9
    assert solver_counters("block_steps") == 9
    np.testing.assert_allclose(
        W, _bcd_f64(X, Y, 4, 3, lam), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        W, _per_step_host_fit(X, Y, 4, 3, lam), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("num_iter", [1, 2])
def test_block_ls_host_fit_builds_grams_ahead_one_at_a_time(
    num_iter, solver_counters, monkeypatch
):
    """3 blocks: every Gram but block 0's is dispatched, with its copy to
    the host started, under the previous block's factorisation, and no
    Gram is on the device when the next one is dispatched."""
    import jax

    from keystone_tpu.ops.learning import block_ls, hostsolve

    order = []
    gram_program = block_ls._block_stats_gram
    factor = hostsolve._factor

    def gram_ahead(X, mu, start, *, width, n):
        grams_live = [
            a for a in jax.live_arrays()
            if a.shape == (width, width) and not a.is_deleted()
        ]
        assert not grams_live, "a Gram still on the device"
        order.append(("gram", start))
        return gram_program(X, mu, start, width=width, n=n)

    def factor_in_order(G, lam, sp):
        order.append(("factor", G.shape[0]))
        return factor(G, lam, sp)

    monkeypatch.setattr(block_ls, "_block_stats_gram", gram_ahead)
    monkeypatch.setattr(hostsolve, "_factor", factor_in_order)
    X, Y = _bank_problem(17, d=15)
    est = BlockLeastSquaresEstimator(
        5, num_iter=num_iter, lam=0.1, solve="host"
    )
    W = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    steps = 3 * num_iter
    assert solver_counters("gram_prefetches") == 2
    assert solver_counters("gram_builds") == 3
    assert solver_counters("factor_reuses") == steps - 3
    assert solver_counters("host_solves") == steps
    assert order == [
        ("gram", 0), ("gram", 5), ("factor", 5), ("gram", 10),
        ("factor", 5), ("factor", 5),
    ]
    np.testing.assert_allclose(
        W, _bcd_f64(X, Y, 5, num_iter, 0.1), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_block_stats_gram_and_rhs_are_the_centred_products(dtype):
    """The Gram-only program and the right-hand side's: G_c = X_bᵀX_b −
    n·μ_bμ_bᵀ and X_bᵀR⁺ − μ_b·(1ᵀR⁺) of the centred block, against float64
    products of the centred columns, pad rows and all."""
    from keystone_tpu.ops.learning import block_ls

    X, Y = _bank_problem(18, n=90, d=12)
    data = Dataset.of(X.astype(dtype)).to_array_mode()
    Xp, n, mask = data.padded(), data.n, data.mask()
    mu, _, R = block_ls._prep(
        Xp, Dataset.of(Y).to_array_mode().padded(), mask, n
    )
    X64 = np.asarray(jnp.asarray(X.astype(dtype), jnp.float32), np.float64)
    Xc = X64 - X64.mean(0)
    Rc = Y.astype(np.float64) - Y.mean(0)
    Wb = jnp.asarray(np.random.default_rng(19).standard_normal((4, 3)),
                     jnp.float32)
    gram = block_ls._block_stats_gram(Xp, mu, 4, width=4, n=n)
    rhs, R_plus = block_ls._block_stats_rhs(
        Xp, R, Wb, mu, mask, 4, width=4
    )
    Xb = Xc[:, 4:8]
    R_plus64 = Rc + Xb @ np.asarray(Wb, np.float64)
    np.testing.assert_allclose(
        np.asarray(gram), Xb.T @ Xb, rtol=1e-5, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(R_plus)[:n], R_plus64, rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(rhs), Xb.T @ R_plus64, rtol=1e-5, atol=1e-3
    )


@pytest.mark.parametrize("bad_blocks", [(1,), (0, 2)])
def test_block_ls_host_bank_keeps_the_eigh_form(bad_blocks, solver_counters):
    """A zero column makes the block's Gram singular and ``cho_factor``
    raise: the block takes the eigh form once and is solved against that
    form in every later sweep, with the per-step path's result."""
    X, Y = _bank_problem(11)
    for b in bad_blocks:
        X[:, 4 * b + 1] = 0.0
    est = BlockLeastSquaresEstimator(4, num_iter=3, solve="host")
    W = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    assert solver_counters("host_solve_fallbacks") == len(bad_blocks)
    assert solver_counters("gram_builds") == 3
    assert solver_counters("factor_reuses") == 6
    assert np.all(np.isfinite(W))
    np.testing.assert_allclose(
        W, _per_step_host_fit(X, Y, 4, 3, 0.0), rtol=1e-5, atol=1e-6
    )
    # the per-step path fell back in every sweep
    assert solver_counters("host_solve_fallbacks") == 4 * len(bad_blocks)


class _Interrupt(Exception):
    pass


@pytest.mark.parametrize(
    "die_after,grams,reuses", [(1, 3, 5), (4, 3, 2), (5, 3, 1), (7, 2, 0)]
)
def test_block_ls_host_bank_resumed_fit_builds_each_gram_once(
    die_after, grams, reuses, tmp_path, solver_counters
):
    """A fit resumed from a checkpoint enters a later sweep with an empty
    bank: every block it still visits builds its Gram on the first of
    those visits, whatever the sweep, each but the first built ahead
    under the previous block's factorisation, and reuses the factor
    after; the model is the uninterrupted fit's."""
    import dataclasses

    X, Y = _bank_problem(12)
    Xd, Yd = Dataset.of(X), Dataset.of(Y)
    base = BlockLeastSquaresEstimator(4, num_iter=3, lam=0.1, solve="host")
    W_whole = np.asarray(base.fit(Xd, Yd).W)

    def die(done):
        if done == die_after:
            raise _Interrupt

    path = str(tmp_path / "bls.npz")
    with pytest.raises(_Interrupt):
        dataclasses.replace(
            base, checkpoint_path=path, checkpoint_every=1,
            block_callback=die,
        ).fit(Xd, Yd)
    before = {
        c: solver_counters(c)
        for c in ("gram_builds", "gram_prefetches", "factor_reuses",
                  "block_steps")
    }
    resumed = dataclasses.replace(
        base, checkpoint_path=path, checkpoint_every=1
    )
    W_resumed = np.asarray(resumed.fit(Xd, Yd).W)
    assert solver_counters("block_steps") - before["block_steps"] == (
        9 - die_after
    )
    assert solver_counters("gram_builds") - before["gram_builds"] == grams
    assert solver_counters("gram_prefetches") - before[
        "gram_prefetches"] == grams - 1
    assert solver_counters("factor_reuses") - before["factor_reuses"] == (
        reuses
    )
    np.testing.assert_allclose(W_resumed, W_whole, rtol=2e-4, atol=2e-5)


def test_block_ls_host_bank_does_not_outlive_a_fit(solver_counters):
    """Two fits of one estimator instance on different data of the same
    shape: the second builds its own Grams and gets its own model."""
    est = BlockLeastSquaresEstimator(4, num_iter=3, lam=0.1, solve="host")
    models = []
    for seed in (13, 14):
        X, Y = _bank_problem(seed)
        models.append((X, Y, np.asarray(
            est.fit(Dataset.of(X), Dataset.of(Y)).W
        )))
    assert solver_counters("gram_builds") == 6
    assert solver_counters("factor_reuses") == 12
    for X, Y, W in models:
        np.testing.assert_allclose(
            W, _bcd_f64(X, Y, 4, 3, 0.1), rtol=1e-4, atol=1e-5
        )
    assert np.abs(models[0][2] - models[1][2]).max() > 1e-2


@pytest.mark.parametrize(
    "solve,num_iter", [("host", 1), ("device", 1), ("device", 3)]
)
def test_block_ls_keeps_no_factor_without_a_second_host_sweep(
    solve, num_iter, solver_counters, monkeypatch
):
    """One sweep never comes back to a block, and the device solve factors
    on the chip: neither keeps a factor, both build a Gram every step."""
    from keystone_tpu.ops.learning import block_ls

    def no_kept_factor(*a, **k):
        raise AssertionError("solved against a kept factor")

    monkeypatch.setattr(block_ls, "psd_solve_factored_host", no_kept_factor)
    X, Y = _bank_problem(15)
    est = BlockLeastSquaresEstimator(
        4, num_iter=num_iter, lam=0.1, solve=solve
    )
    W = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    assert solver_counters("factor_reuses") == 0
    assert solver_counters("gram_builds") == 3 * num_iter
    assert solver_counters("gram_prefetches") == (2 if solve == "host" else 0)
    assert solver_counters("block_steps") == 3 * num_iter
    np.testing.assert_allclose(
        W, _bcd_f64(X, Y, 4, num_iter, 0.1), rtol=2e-3, atol=2e-4
    )


@pytest.mark.parametrize("form", ["cholesky", "eigh"])
def test_host_factor_solves_later_rhs_as_a_whole_solve_would(
    form, solver_counters
):
    """The two halves of ``psd_solve_host``: the factor handed back with
    the first solution solves another right-hand side to the result of a
    whole solve, in the form the first solve took."""
    from keystone_tpu.ops.learning.hostsolve import (
        factor_solve_host,
        psd_solve_factored_host,
        psd_solve_host,
    )

    rng = np.random.default_rng(16)
    A = rng.standard_normal((20, 6))
    G = A.T @ A
    if form == "eigh":
        G[2, :] = G[:, 2] = 0.0  # a zero pivot: cho_factor raises
    rhs1, rhs2 = rng.standard_normal((2, 6, 3))
    if form == "eigh":
        rhs1[2] = rhs2[2] = 0.0
    lam = 0.0 if form == "eigh" else 0.05
    W1, factor = factor_solve_host(G, rhs1, lam)
    assert factor.form == form
    assert solver_counters("host_solve_fallbacks") == (form == "eigh")
    np.testing.assert_array_equal(W1, psd_solve_host(G, rhs1, lam))
    np.testing.assert_array_equal(
        psd_solve_factored_host(factor, rhs2), psd_solve_host(G, rhs2, lam)
    )
    assert solver_counters("host_solves") == 4
    ok = [i for i in range(6) if form != "eigh" or i != 2]
    np.testing.assert_allclose(
        (G + lam * np.eye(6))[np.ix_(ok, ok)] @ W1[ok], rhs1[ok],
        rtol=1e-9, atol=1e-9,
    )


def test_read_back_ahead_is_read_back_and_frees_the_device_copy(
    solver_counters
):
    """A Gram read back ahead, on the read-back thread, is the float64
    copy ``read_back`` makes of it, its device array is deleted once the
    host has it, and its bytes are counted once, beside the right-hand
    side's."""
    from keystone_tpu.ops.learning.hostsolve import (
        read_back,
        read_back_ahead,
    )

    rng = np.random.default_rng(20)
    gram = jnp.asarray(rng.standard_normal((6, 6)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((6, 2)), jnp.float32)
    G_now, _ = read_back(gram, rhs)
    handle = read_back_ahead(gram)
    G, R = read_back(handle, rhs)
    assert gram.is_deleted()
    assert G.dtype == R.dtype == np.float64
    np.testing.assert_array_equal(G, G_now)
    np.testing.assert_array_equal(R, np.asarray(rhs, np.float64))
    assert solver_counters("readback_bytes") == 2 * (36 + 12) * 4


# blocks of 640 (``_sym_gram`` cuts them 384 | 256) and a last one of 256
@pytest.mark.parametrize("solve", ["device", "host"])
def test_block_ls_on_blocks_wider_than_the_gram_leaf(solve, solver_counters):
    from keystone_tpu.ops.learning import block_ls

    X, Y = _bank_problem(4, n=2048, d=1536)
    Y /= np.sqrt(1536, dtype=np.float32)
    sweeps, lam = 2, 5.0
    est = BlockLeastSquaresEstimator(640, num_iter=sweeps, lam=lam, solve=solve)
    W = np.asarray(est.fit(Dataset.of(X), Dataset.of(Y)).W)
    np.testing.assert_allclose(
        W, _bcd_f64(X, Y, 640, sweeps, lam), rtol=1e-4, atol=1e-5)
    # a Gram every block step on the device, one a block with the host's
    # kept factors: counted as before, and its column pairs beside it
    fits_of_grams = sweeps if solve == "device" else 1
    assert solver_counters("gram_builds") == 3 * fits_of_grams
    assert solver_counters("block_steps") == 3 * sweeps
    wide, _ = block_ls._gram_pairs(640)
    assert wide == 384 * 384 + 384 * 256 + 256 * 256
    assert solver_counters("gram_pairs_computed") == \
        fits_of_grams * (2 * wide + 256 * 256)
    assert solver_counters("gram_pairs") == \
        fits_of_grams * (2 * 640 * 640 + 256 * 256)


def test_gram_pairs_share_metrics_read_the_program_s_counters(
        solver_counters):
    """The benchmark's ``gram_pairs_share.*`` are data files over two
    counters: 1.0 while every block is at or under the leaf, 0.5625 for
    blocks of 4,096, and no Gram more is counted for either."""
    import json
    import os

    from benchmark.readers import counter_ratio
    from keystone_tpu.ops.learning import block_ls

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, cell in [("gram_pairs_share.wfit", "weighted-bcd-fit"),
                       ("gram_pairs_share.cfit", "cifar-fit"),
                       ("gram_pairs_share.fit", "timit-fit")]:
        with open(os.path.join(root, "benchmark", "metrics",
                               name + ".json")) as f:
            metric = json.load(f)
        assert metric == {"reader": "counter_ratio", "args": {
            "numerator": "keystone_solver_gram_pairs_computed_total",
            "denominator": "keystone_solver_gram_pairs_total"}}
        assert [m for m in manifest["per_layer"] if m["name"] == name] == [{
            "name": name, "unit": "share", "better": "lower",
            "source": "program_counter", "layer": "Solvers",
            "moves": "fit_rows_per_s", "workloads": [cell]}]
    assert counter_ratio.read(None, **metric["args"]) is None  # no fit yet
    X, Y = _bank_problem(5, n=64, d=24, k=2)
    BlockLeastSquaresEstimator(8, lam=0.1).fit(Dataset.of(X), Dataset.of(Y))
    assert solver_counters("gram_builds") == 3
    assert counter_ratio.read(None, **metric["args"]) == 1.0
    block_ls._count_gram_pairs([4096, 4096], times=3)
    assert solver_counters("gram_builds") == 3
    assert solver_counters("gram_pairs") == 3 * 64 + 6 * 4096 ** 2
    assert solver_counters("gram_pairs_computed") == \
        3 * 64 + 6 * 0.5625 * 4096 ** 2
