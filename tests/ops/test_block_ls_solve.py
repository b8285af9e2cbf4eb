"""The default device solve of the block solver has to compile at the
applications' block widths: no fall-back under lax.cond from a few
thousand columns up (PERF.md section 7, fault 1). At or under the
fall-back's width a block whose Cholesky breaks down is solved by the
factor of A + δI refined against A, counted, and read once a fit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.learning import block_ls
from keystone_tpu.ops.learning.block_ls import BlockLeastSquaresEstimator
from keystone_tpu.parallel.dataset import Dataset


def solve_jaxpr(width: int, classes: int = 10) -> str:
    a = jax.ShapeDtypeStruct((width, width), jnp.float32)
    rhs = jax.ShapeDtypeStruct((width, classes), jnp.float32)
    return str(jax.make_jaxpr(
        lambda *args: block_ls._psd_solve_with_factor(*args)[0])(a, a, rhs))


# RandomPatchCifar's last block and the applications' block width
@pytest.mark.parametrize("width", [2176, 4096])
def test_no_fallback_at_the_applications_widths(width):
    text = solve_jaxpr(width)
    assert "cond" not in text and "cholesky" not in text
    assert "triangular_solve" in text


@pytest.mark.parametrize("width", [64, block_ls._FALLBACK_MAX_WIDTH])
def test_small_widths_keep_the_fallback(width):
    text = solve_jaxpr(width)
    assert "cond" in text and "cholesky" in text and "eigh" not in text


def test_breakdown_surfaces_as_a_non_finite_model(monkeypatch):
    """Without the fall-back an indefinite system gives a model that is
    not finite, which wide callers assert on."""
    monkeypatch.setattr(block_ls, "_FALLBACK_MAX_WIDTH", 4)
    a = -jnp.eye(8, dtype=jnp.float32)
    w = block_ls._psd_solve_device(a, jnp.ones((8, 2), jnp.float32), 0.0)
    assert not np.all(np.isfinite(np.asarray(w)))
    good = block_ls._psd_solve_device(
        jnp.eye(8, dtype=jnp.float32) * 4.0, jnp.ones((8, 2), jnp.float32), 0.0)
    np.testing.assert_allclose(np.asarray(good), 0.25, rtol=1e-6)


@pytest.fixture
def counters():
    from keystone_tpu.observability.registry import (
        get_global_registry,
        reset_global_registry,
    )

    reset_global_registry()
    yield lambda name: get_global_registry().counter(
        "keystone_solver_" + name + "_total").get()
    reset_global_registry()


def _rectified(seed: int, n: int = 200, d: int = 48):
    """Rectified random projections (the MNIST featurizer's kind of
    column) with columns 3 and 40 never positive: two zero columns, so
    blocks 0 and 2 of 16 are singular at lam 0."""
    rng = np.random.default_rng(seed)
    x = np.maximum(rng.standard_normal((n, d)), 0.0)
    x[:, 3] = 0.0
    x[:, 40] = 0.0
    y = rng.standard_normal((n, 3))
    return x.astype(np.float32), y.astype(np.float32)


def _pinv_sweep(x, y, block):
    """One centred Gauss-Seidel sweep in float64, each block by the
    pseudo-inverse (a zero column's weight is zero)."""
    xc = np.asarray(x, np.float64) - x.mean(0)
    r = np.asarray(y, np.float64) - y.mean(0)
    w = np.zeros((x.shape[1], y.shape[1]))
    for s in range(0, x.shape[1], block):
        a = xc[:, s:s + block]
        w[s:s + block] = np.linalg.pinv(a.T @ a) @ (a.T @ r)
        r -= a @ w[s:s + block]
    return w


def test_singular_block_takes_the_fallback_and_is_counted(counters):
    """At lam 0 a block with a zero column breaks the float32 Cholesky:
    the fall-back solves it as the float64 pseudo-inverse does, the
    fit's one read counts it beside every device solve, and the model is
    finite."""
    x, y = _rectified(0)
    model = BlockLeastSquaresEstimator(16, num_iter=1, lam=0.0).fit(
        Dataset.from_array(jnp.asarray(x)), Dataset.from_array(jnp.asarray(y)))
    assert counters("device_block_solves") == 3
    assert counters("factor_fallbacks") == 2
    got = np.asarray(model.W, np.float64)
    assert got[3].tolist() == [0.0] * 3 and got[40].tolist() == [0.0] * 3
    want = _pinv_sweep(x, y, 16)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("rank", [5, 11])
def test_linear_features_of_lower_rank_solve_as_the_pseudo_inverse(
        counters, rank):
    """At lam 0 features linear in fewer inputs than a block is wide (the
    featurizer with its rectifier dropped) leave every block singular and
    its float32 Gram indefinite by rounding: the fall-back gives a finite
    model whose fit is the float64 pseudo-inverse's. Its weights carry
    rounding noise in the directions no row reaches (the noise over the
    first ridge), which no fit can see and which stays of the
    pseudo-inverse's size."""
    rng = np.random.default_rng(rank)
    z = rng.standard_normal((240, rank))
    x = (z @ rng.standard_normal((rank, 32))).astype(np.float32)
    y = rng.standard_normal((240, 3)).astype(np.float32)
    model = BlockLeastSquaresEstimator(16, num_iter=1, lam=0.0).fit(
        Dataset.from_array(jnp.asarray(x)), Dataset.from_array(jnp.asarray(y)))
    assert counters("device_block_solves") == 2
    assert counters("factor_fallbacks") >= 1
    got = np.asarray(model.W, np.float64)
    assert np.all(np.isfinite(got))
    want = _pinv_sweep(x, y, 16)
    xc = np.asarray(x, np.float64) - x.mean(0)
    fit, fit_want = xc @ got, xc @ want
    assert np.linalg.norm(fit - fit_want) / np.linalg.norm(fit_want) < 1e-3
    assert np.linalg.norm(got) < 3.0 * np.linalg.norm(want)


@pytest.mark.parametrize("system", ["indefinite", "zero"])
def test_fallback_grows_its_ridge_until_the_factor_holds(system):
    """A Gram that rounding left with an eigenvalue a thousand times below
    the first ridge's -δ breaks that factor too: δ grows until A + δI
    factors, and the solve is finite. A Gram of zeros factors at the
    first step and solves a zero right-hand side as zero."""
    rng = np.random.default_rng(7)
    q, _ = np.linalg.qr(rng.standard_normal((24, 24)))
    if system == "indefinite":
        eig = np.linspace(1.0, 0.1, 24)
        eig[-1] = -1e-3
        rhs = rng.standard_normal((24, 2))
    else:
        eig = np.zeros(24)
        rhs = np.zeros((24, 2))
    a = jnp.asarray((q * eig) @ q.T, jnp.float32)
    rhs = jnp.asarray(rhs, jnp.float32)
    first = jax.scipy.linalg.cholesky(
        a + block_ls._FALLBACK_RIDGE * jnp.eye(24), lower=True)
    assert np.all(np.isfinite(np.asarray(first))) == (system == "zero")
    w, fell_back = block_ls._psd_solve_with_factor(
        a, jax.scipy.linalg.cholesky(a, lower=True), rhs)
    w = np.asarray(w, np.float64)
    assert bool(fell_back) and np.all(np.isfinite(w))
    if system == "zero":
        assert not np.any(w)
        return
    # the directions well above the grown ridge solve as A's inverse does
    keep = q[:, :20]
    want = keep @ ((keep.T @ np.asarray(rhs, np.float64)) / eig[:20, None])
    np.testing.assert_allclose(keep @ (keep.T @ w), want, rtol=0,
                               atol=1e-3 * max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("lam", [0.0, 10.0])
def test_regular_blocks_fall_back_nowhere(counters, lam):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((200, 32)).astype(np.float32)
    y = rng.standard_normal((200, 2)).astype(np.float32)
    BlockLeastSquaresEstimator(16, num_iter=2, lam=lam).fit(
        Dataset.from_array(jnp.asarray(x)), Dataset.from_array(jnp.asarray(y)))
    assert counters("device_block_solves") == 4
    assert counters("factor_fallbacks") == 0


def test_a_model_that_is_not_finite_raises(monkeypatch, counters):
    """Above the fall-back's width a breakdown leaves NaN in the model:
    the fit's one read finds it and raises, naming the remedy."""
    monkeypatch.setattr(block_ls, "_FALLBACK_MAX_WIDTH", 4)
    x, y = _rectified(2, n=208)  # rows no other test traces the step at
    with pytest.raises(FloatingPointError, match="not finite"):
        BlockLeastSquaresEstimator(16, num_iter=1, lam=0.0).fit(
            Dataset.from_array(jnp.asarray(x)),
            Dataset.from_array(jnp.asarray(y)))
    assert counters("device_block_solves") == 3
    assert counters("factor_fallbacks") == 0
