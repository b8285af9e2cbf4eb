"""Kernel ridge tests (reference: KernelModelSuite — block solve vs exact
dual solution)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.learning.kernel import (
    GaussianKernelGenerator,
    KernelRidgeRegression,
)
from keystone_tpu.parallel.dataset import Dataset


def _rbf(A, B, gamma):
    d2 = (
        (A * A).sum(1)[:, None]
        + (B * B).sum(1)[None, :]
        - 2 * A @ B.T
    )
    return np.exp(-gamma * np.maximum(d2, 0))


def test_kernel_block(mesh8):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((40, 5)).astype(np.float32)
    gen = GaussianKernelGenerator(gamma=0.3)
    t = gen.fit(Dataset.of(X).shard())
    km = t.kernel_matrix(Dataset.of(X).shard())
    K = _rbf(X, X, 0.3)
    got = np.asarray(km.block(0, 16))
    # valid region matches to the documented kernel-generation contract:
    # the cross GEMM uses the 3-pass BF16_BF16_F32_X3 algorithm
    # (kernel.py _cross_mm_x3, ~1.5e-5 relative on the dot products →
    # up to ~1e-4-level kernel error ON-CHIP after the γ·d² exponent;
    # CPU emulates the algorithm more accurately, so the CPU bar stays
    # tight); solution-level accuracy is pinned separately by
    # test_krr_matches_reference_translation
    import jax

    atol = 1e-3 if jax.devices()[0].platform != "cpu" else 1e-4
    np.testing.assert_allclose(got[:40, :16], K[:, :16], atol=atol)
    assert np.allclose(got[40:], 0)


def _np_gauss_seidel(K, Y, lam, block_size, num_epochs):
    """numpy translation of KernelRidgeRegression.scala:86-235."""
    n = K.shape[0]
    W = np.zeros((n, Y.shape[1]))
    for _ in range(num_epochs):
        for s in range(0, n, block_size):
            e = min(s + block_size, n)
            Kb = K[:, s:e]
            Kbb = K[s:e, s:e]
            rhs = Y[s:e] - (Kb.T @ W - Kbb.T @ W[s:e])
            W[s:e] = np.linalg.solve(Kbb + lam * np.eye(e - s), rhs)
    return W


def test_krr_matches_reference_translation(mesh8):
    """Same epochs => same iterates as the reference algorithm."""
    rng = np.random.default_rng(1)
    n = 60
    X = rng.standard_normal((n, 4)).astype(np.float32)
    Y = rng.standard_normal((n, 3)).astype(np.float32)
    gamma, lam = 0.5, 0.1
    est = KernelRidgeRegression(
        GaussianKernelGenerator(gamma), lam, block_size=16, num_epochs=5
    )
    model = est.fit(Dataset.of(X).shard(), Dataset.of(Y).shard())
    K = _rbf(X, X, gamma).astype(np.float64)
    W_ref = _np_gauss_seidel(K, Y.astype(np.float64), lam, 16, 5)
    np.testing.assert_allclose(
        np.asarray(model.model)[:n], W_ref, atol=1e-3
    )


def test_krr_converges_to_exact(mesh8):
    """Well-conditioned regime: iterates reach the exact dual solution."""
    rng = np.random.default_rng(1)
    n = 60
    X = rng.standard_normal((n, 4)).astype(np.float32)
    Y = rng.standard_normal((n, 3)).astype(np.float32)
    gamma, lam = 0.5, 2.0
    est = KernelRidgeRegression(
        GaussianKernelGenerator(gamma), lam, block_size=16, num_epochs=30
    )
    model = est.fit(Dataset.of(X).shard(), Dataset.of(Y).shard())
    K = _rbf(X, X, gamma).astype(np.float64)
    W_exact = np.linalg.solve(K + lam * np.eye(n), Y.astype(np.float64))
    np.testing.assert_allclose(
        np.asarray(model.model)[:n], W_exact, atol=5e-3
    )
    # train predictions via blockwise apply match K @ W
    pred = np.asarray(model.apply_batch(Dataset.of(X)).array())
    np.testing.assert_allclose(pred, K @ W_exact, atol=5e-2)


def test_krr_single_apply(mesh8):
    rng = np.random.default_rng(2)
    X = rng.standard_normal((30, 4)).astype(np.float32)
    Y = rng.standard_normal((30, 2)).astype(np.float32)
    est = KernelRidgeRegression(
        GaussianKernelGenerator(0.4), 0.2, block_size=8, num_epochs=10
    )
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    batch = np.asarray(model.apply_batch(Dataset.of(X)).array())
    one = np.asarray(model.apply(X[0]))
    np.testing.assert_allclose(one, batch[0], atol=1e-4)


def test_krr_block_permutation_still_converges(mesh8):
    rng = np.random.default_rng(3)
    n = 48
    X = rng.standard_normal((n, 3)).astype(np.float32)
    Y = rng.standard_normal((n, 2)).astype(np.float32)
    est = KernelRidgeRegression(
        GaussianKernelGenerator(0.5), 2.0, block_size=16, num_epochs=30,
        block_permuter=7,
    )
    model = est.fit(Dataset.of(X), Dataset.of(Y))
    K = _rbf(X, X, 0.5).astype(np.float64)
    W_exact = np.linalg.solve(K + 2.0 * np.eye(n), Y.astype(np.float64))
    np.testing.assert_allclose(np.asarray(model.model)[:n], W_exact, atol=1e-2)


def test_krr_cached_kernel_matches_uncached():
    """cache_kernel=True (prebuilt column blocks + batched diagonal
    Cholesky bank) must reproduce the regenerate-per-block scan — same
    math, restructured schedule (kernel.py _krr_cached_epoch_scan)."""
    import dataclasses as dc

    rng = np.random.default_rng(11)
    n, d, k = 96, 5, 3
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    Xd = Dataset.from_array(jnp.asarray(X))
    Yd = Dataset.from_array(jnp.asarray(Y))
    base = KernelRidgeRegression(
        GaussianKernelGenerator(gamma=0.2), lam=0.3, block_size=32,
        num_epochs=3, block_permuter=5,
    )
    W_cached = np.asarray(
        dc.replace(base, cache_kernel=True).fit(Xd, Yd).model
    )
    W_plain = np.asarray(
        dc.replace(base, cache_kernel=False).fit(Xd, Yd).model
    )
    np.testing.assert_allclose(W_cached, W_plain, rtol=2e-5, atol=1e-6)
    # and both sit on the reference iterates
    K = _rbf(X, X, 0.2).astype(np.float64)
    W_ref = _np_gauss_seidel_perm(K, Y.astype(np.float64), 0.3, 32, 3, 5)
    np.testing.assert_allclose(W_cached[:n], W_ref, atol=1e-3)


def _np_gauss_seidel_perm(K, Y, lam, block_size, num_epochs, permuter):
    """_np_gauss_seidel with the estimator's per-epoch block permutation."""
    n = K.shape[0]
    W = np.zeros((n, Y.shape[1]))
    n_blocks = (n + block_size - 1) // block_size
    for epoch in range(num_epochs):
        order = list(range(n_blocks))
        np.random.default_rng((permuter, epoch)).shuffle(order)
        for b in order:
            s = b * block_size
            e = min(s + block_size, n)
            Kb = K[:, s:e]
            Kbb = K[s:e, s:e]
            rhs = Y[s:e] - (Kb.T @ W - Kbb.T @ W[s:e])
            W[s:e] = np.linalg.solve(Kbb + lam * np.eye(e - s), rhs)
    return W


def test_krr_device_solve_matches_host_solve():
    import dataclasses as dc

    rng = np.random.default_rng(9)
    n, d, k = 96, 6, 2
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    Xd = Dataset.from_array(jnp.asarray(X))
    Yd = Dataset.from_array(jnp.asarray(Y))
    base = KernelRidgeRegression(
        GaussianKernelGenerator(gamma=0.1), lam=0.4, block_size=32,
        num_epochs=2,
    )
    W_dev = np.asarray(dc.replace(base, solve="device").fit(Xd, Yd).model)
    W_host = np.asarray(dc.replace(base, solve="host").fit(Xd, Yd).model)
    np.testing.assert_allclose(W_dev, W_host, rtol=5e-4, atol=5e-5)


# -- spans, counters and the copies a fit no longer makes (PR 33) ---------


def _krr_counters():
    from keystone_tpu.observability.registry import get_global_registry

    out = {}
    for family in get_global_registry().collect():
        if family.name.startswith("keystone_solver_krr_"):
            for s in family.samples:
                out[(family.name, tuple(sorted(s.labels.items())))] = s.value
    return out


# 96 rows in blocks of 32 (three equal blocks) or 40 (a ragged last one),
# two epochs: (estimator settings, the path, the spans inside .dispatch,
# column blocks generated)
@pytest.mark.parametrize("settings,path,inner,generated", [
    (dict(block_size=32, cache_kernel=False), "scan", [], 6),
    (dict(block_size=32, cache_kernel=True), "cached", [], 3),
    (dict(block_size=40), "block", [], 6),
    (dict(block_size=32, solve="host"), "host",
     ["host_solve", "kernel_block", "residual", "update"], 6),
])
def test_krr_spans_and_counters_name_the_path(settings, path, inner,
                                              generated):
    """Once a fit: .prep, .dispatch and .converged; the per-block host
    path's four spans once a block step; the counters say which path ran,
    how many block steps, and how many column blocks were really
    generated (a cached fit generates each once)."""
    from keystone_tpu.observability.tracing import (
        disable_tracing,
        enable_tracing,
    )

    rng = np.random.default_rng(21)
    X = Dataset.from_array(rng.standard_normal((96, 5)).astype(np.float32))
    Y = Dataset.from_array(rng.standard_normal((96, 3)).astype(np.float32))
    est = KernelRidgeRegression(
        GaussianKernelGenerator(gamma=0.2), lam=0.3, num_epochs=2,
        **settings)
    steps = 2 * -(-96 // settings["block_size"])
    before = _krr_counters()
    tracer = enable_tracing()
    try:
        tracer.clear()
        model = est.fit(X, Y)
        names = [s.name[len("solver.krr."):] for s in tracer.recent()
                 if s.name.startswith("solver.krr.")]
    finally:
        disable_tracing()
    after = _krr_counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after
             if after[k] != before.get(k, 0.0)}
    assert np.all(np.isfinite(np.asarray(model.model)))
    assert sorted(set(names)) == sorted(
        ["prep", "dispatch", "converged"] + inner)
    for name in ("prep", "dispatch", "converged"):
        assert names.count(name) == 1
    for name in inner:
        assert names.count(name) == steps
    assert delta == {
        ("keystone_solver_krr_fits_total", ()): 1,
        ("keystone_solver_krr_path_total", (("path", path),)): 1,
        ("keystone_solver_krr_block_steps_total", ()): steps,
        ("keystone_solver_krr_kernel_blocks_total", ()): generated,
    }


def test_krr_block_steps_metric_reads_the_program_s_counters(monkeypatch):
    """benchmark/metrics/krr_block_steps_per_fit.kfit.json, through the
    benchmark's own reader, on a registry of this test's own: nothing
    before a fit, then the block steps a fit."""
    import json
    import os

    from benchmark.readers import counter_ratio
    from keystone_tpu.observability import registry

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "metrics",
                           "krr_block_steps_per_fit.kfit.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counter_ratio"
    monkeypatch.setattr(registry, "_global_registry",
                        registry.MetricsRegistry())
    assert counter_ratio.read(None, **metric["args"]) is None
    rng = np.random.default_rng(22)
    X = Dataset.from_array(rng.standard_normal((64, 4)).astype(np.float32))
    Y = Dataset.from_array(rng.standard_normal((64, 2)).astype(np.float32))
    est = KernelRidgeRegression(
        GaussianKernelGenerator(gamma=0.2), lam=0.3, block_size=16,
        num_epochs=1)
    est.fit(X, Y)
    est.fit(X, Y)
    assert counter_ratio.read(None, **metric["args"]) == 4.0


def test_krr_model_holds_the_rows_it_was_given_and_no_copy():
    """With no pad row the fitted model's train set IS the array the
    solver was given (2 GB a model at 125,000 x 4,096); pad rows are
    zeroed in a copy as before."""
    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.standard_normal((48, 4)).astype(np.float32))
    whole = GaussianKernelGenerator(gamma=0.1).fit(Dataset.from_array(x))
    assert whole.train_X is x
    padded = GaussianKernelGenerator(gamma=0.1).fit(
        Dataset.from_array(x, n=40))
    assert padded.train_X is not x
    assert np.all(np.asarray(padded.train_X)[40:] == 0.0)
    np.testing.assert_array_equal(
        np.asarray(padded.train_X)[:40], np.asarray(x)[:40])
    np.testing.assert_allclose(
        np.asarray(whole._norms), (np.asarray(x) ** 2).sum(axis=1),
        rtol=1e-6)


def test_krr_breakdown_without_a_fallback_is_an_error(monkeypatch):
    """A block wider than the fall-back's limit whose Cholesky
    breaks down gives a non-finite model: fit says so and returns
    none (duplicated rows, no ridge: K_BB is singular)."""
    from keystone_tpu.ops.learning import block_ls

    monkeypatch.setattr(block_ls, "_FALLBACK_MAX_WIDTH", 8)
    rng = np.random.default_rng(24)
    x = rng.standard_normal((16, 3)).astype(np.float32)
    x = np.concatenate([x, x])  # every row twice
    y = rng.standard_normal((32, 2)).astype(np.float32)
    est = KernelRidgeRegression(
        GaussianKernelGenerator(gamma=0.1), lam=-1e-3, block_size=32,
        num_epochs=1)
    with pytest.raises(FloatingPointError, match="not finite"):
        est.fit(Dataset.from_array(jnp.asarray(x)),
                Dataset.from_array(jnp.asarray(y)))
