"""Block coordinate descent least squares — the workhorse solver.

Reference: nodes/learning/BlockLinearMapper.scala — BlockLinearMapper
(:22,50-73) applies a block-split linear model; BlockLeastSquaresEstimator
(:199-283) mean-centers features/labels per block and runs mlmatrix
BlockCoordinateDescent.solveLeastSquaresWithL2 (Gauss-Seidel sweeps: per
block, executors compute AᵀA / AᵀR Grams, tree-reduce to the driver, driver
solves the (b×b) system, broadcasts the block model, executors update the
residual).

TPU-native redesign: the feature matrix is ONE sharded (n, D) array (rows
over the mesh's data axis) instead of a Seq of per-block RDDs; a block is a
static column slice. Each block update is a single jitted program:

    R⁺   = R + X_b W_b            (undo this block's contribution)
    G    = X_bᵀ X_b               (per-shard MXU matmuls + psums over "data";
                                   the upper block triangle only: _sym_gram)
    W_b' = (G + λI)⁻¹ X_bᵀ R⁺      (f64 host solve — see hostsolve.py)
    R    = R⁺ − X_b W_b'

so the reference's executor-GEMM → treeReduce → driver-solve → broadcast →
residual-update round trip collapses into two XLA programs around one small
host solve; the O(n·b·(b+k)) work never leaves the device, and the residual
buffer is donated to avoid an HBM copy per block.

G depends on X and μ only, so with ``solve="host"`` and more than one sweep
a fit builds, reads back and factors each block's G once, on the block's
first visit in that call of ``fit()``, and keeps the float64 factor on the
host until the call returns (the reference's weighted solver caches its
BlockStatistics across passes the same way). Every visit runs
``_block_stats_rhs`` (R⁺ and X_bᵀR⁺); later visits read back the (b, k)
right-hand side alone and solve against the kept factor. For the same
reason G is built a block ahead: ``_block_stats_gram`` of the next block
to be factored is dispatched, and its read-back started
(``hostsolve.read_back_ahead``: the copy to the host, then the float64
conversion on the read-back thread), once this block's G has been read
back and deleted on the device and before this block's ``cho_factor``,
so block i+1's Gram program, its 64 MB copy and its conversion run under
block i's factorisation (block 0's right after ``solver.prep``). One
Gram at most is on the device; the host holds a second while it factors.

Observability: host spans ``solver.prep``, ``solver.gram_ahead`` (the
Gram's dispatch and the start of its read-back: once after the prep, then
inside each factoring step but the last, between its read-back and its
factorisation), then per block step ``solver.block_stats`` →
``solver.readback`` → ``solver.host_solve`` → ``solver.upload`` →
``solver.residual_update`` (``solve="host"``) or ``solver.block_step``
(device solve), and after a device solve's last block
``solver.converged`` (its one read: how many blocks' Cholesky broke
down and fell back to the ridged factor, and whether the model is
finite — a fit whose model is not raises); on the device
``jax.named_scope`` names ``solver.residual_plus`` / ``solver.gram`` /
``solver.rhs`` / ``solver.solve`` / ``solver.residual`` /
``solver.prep``; counters
``keystone_solver_fits_total``, ``_block_steps_total``,
``_gram_builds_total`` (Grams really built), ``_gram_prefetches_total``
(those whose copy started under the previous block's factorisation),
``_gram_pairs_computed_total`` over ``_gram_pairs_total`` (the share of a
full product's column pairs those Grams multiplied),
``_factor_reuses_total``, ``_device_block_solves_total`` and
``_factor_fallbacks_total`` (device solves, and those of them that fell
back; hostsolve.py has the host solve's).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import LabelEstimator, Transformer
from keystone_tpu.ops.learning.hostsolve import (
    HostFactor,
    factor_solve_host,
    psd_solve_factored_host,
    read_back,
    read_back_ahead,
)
from keystone_tpu.utils.checkpoint import (
    LoopCheckpointer,
    data_probe,
    two_level_schedule,
)


def _f32_mm(a, b):
    """Matmul with f32 accumulation. bf16 inputs ride the MXU's native
    bf16xbf16->f32 path; f32 inputs request HIGHEST precision — on TPU
    the DEFAULT precision truncates f32 operands to bf16 passes, and the
    centered-Gram algebra (G − n·μμᵀ) cancels ~3 orders of magnitude, so
    default-precision f32 Grams come out with O(1) relative error
    (measured: 789 abs err vs 0.09 at HIGHEST on a 256x1024 relu-FFT
    feature Gram, which silently destroyed the MNIST app's model). Users
    choose speed by passing bf16 data, not by losing f32 semantics."""
    f32_in = a.dtype == jnp.float32 or b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32_in else None,
    )


# Widest block whose Gram is still one full product (``_sym_gram``).
_GRAM_LEAF = 512


def _gram_cut(width: int) -> int:
    """Where ``_sym_gram`` cuts a block of ``width`` columns in two: the
    multiple of 128 (one MXU width) nearest the middle, the larger half
    first on a tie; 0 for a block at or under the leaf, which is not
    cut."""
    return 0 if width <= _GRAM_LEAF else 128 * ((width + 128) // 256)


def _gram_pairs(width: int):
    """(column pairs ``_sym_gram`` multiplies, column pairs of the whole
    Gram) for a block of ``width`` columns: Σ over its products of left
    width × right width, and width². Follows the same cuts."""
    cut = _gram_cut(width)
    if not cut:
        return width * width, width * width
    return (
        _gram_pairs(cut)[0] + cut * (width - cut)
        + _gram_pairs(width - cut)[0],
        width * width,
    )


def _sym_gram(Xb):
    """X_bᵀX_b from its upper block triangle, for every solve that forms
    a block Gram (f32 accumulation and ``_f32_mm``'s precision policy:
    f32 rows at HIGHEST, bf16 rows on the native path).

    A block wider than ``_GRAM_LEAF`` is cut in two (``_gram_cut``) and
        G = [[sym(X₁), X₁ᵀX₂], [(X₁ᵀX₂)ᵀ, sym(X₂)]]
    recursively; at or under the leaf, the one full product. Every entry
    is the same dot product over the same rows at the same precision as
    in the full product, the lower off-diagonal blocks are the exact
    transposes of the upper, and the MXU work is (1 + 2^−d)/2 of the
    full product after d cuts (0.5625 at width 4,096). The column slices
    fuse into the products (no row-sized copy), and each product's
    contraction over a sharded example axis is still a per-shard MXU
    matmul + a psum over the data axis."""
    cut = _gram_cut(Xb.shape[1])
    if not cut:
        return _f32_mm(Xb.T, Xb)
    X1, X2 = Xb[:, :cut], Xb[:, cut:]
    G12 = _f32_mm(X1.T, X2)
    return jnp.concatenate(
        [
            jnp.concatenate([_sym_gram(X1), G12], axis=1),
            jnp.concatenate([G12.T, _sym_gram(X2)], axis=1),
        ],
        axis=0,
    )


# widest system that carries the fall-back factorisation (RandomPatchCifar's
# last block is 2,176 wide, the applications' blocks 4,096)
_FALLBACK_MAX_WIDTH = 2048

# the fall-back factors A + δ·I, δ = _FALLBACK_RIDGE·max(diag A) first
# and _FALLBACK_GROWTH times more after each breakdown
_FALLBACK_RIDGE = 1e-6
_FALLBACK_GROWTH = 10.0


def _psd_solve_with_factor(A, L, rhs, refine=2):
    """(X, fell back) with A X = rhs, given A's (already-ridged)
    Cholesky factor ``L``: f32 + ``refine`` iterative-refinement steps.
    Refinement recovers most of the f64 accuracy the reference's
    driver-side LAPACK solve had (mlmatrix NormalEquations;
    BlockLinearMapper.scala:234-240) without a host round-trip: the
    solve stays inside the async dispatch stream. Where Cholesky breaks
    down (an f32 Gram that rounding left indefinite, or a singular one at
    lam 0: a feature column that is all zeros, features of lower rank
    than the block), falls back to the factor of A + δI, refined against
    A itself: δ starts at a millionth of A's largest diagonal entry and
    grows tenfold while the factor breaks down, up to the width times
    that entry, where A + δI is diagonally dominant and factors whatever
    rounding did to A. Directions well above δ are solved as the
    Cholesky path solves them, and a null direction whose right-hand
    side is zero stays zero (PERF.md: an eigh fall-back with clamped
    eigenvalues read 25 to 2,000 times the Cholesky path's error on such
    blocks). "fell back" is a device bool, always false above the
    fall-back's width. Shared by the fresh-factor path below and the
    cached-KRR factor bank (kernel.py _krr_cached_epoch_scan)."""
    # full-f32 matmuls: refinement converges to the residual's noise
    # floor, so the default bf16 matmul passes would cap the recovered
    # accuracy ~3 digits short
    hp = jax.lax.Precision.HIGHEST

    def chol_path(L):
        def solve(b):
            return jax.scipy.linalg.cho_solve((L, True), b)

        W = solve(rhs)
        for _ in range(refine):
            W = W + solve(rhs - jnp.matmul(A, W, precision=hp))
        return W

    if A.shape[0] > _FALLBACK_MAX_WIDTH:
        # No fall-back from a block of a few thousand columns up:
        # lax.cond compiles both branches into every block program, and
        # the applications' wide blocks, regularized fits well inside
        # chol's range, keep one factorisation. A breakdown there
        # surfaces as a non-finite W, which the solvers raise on.
        return chol_path(L), jnp.zeros((), jnp.bool_)

    def ridged_path(L):
        top = jnp.max(jnp.diagonal(A))
        top = jnp.where(top > 0, top, 1.0)  # a zero Gram still factors
        eye = jnp.eye(A.shape[0], dtype=A.dtype)

        def broken(state):
            delta, L = state
            return ~jnp.all(jnp.isfinite(L)) & (delta < A.shape[0] * top)

        def grow(state):
            delta = state[0] * _FALLBACK_GROWTH
            return delta, jax.scipy.linalg.cholesky(A + delta * eye,
                                                    lower=True)

        # the first step factors at _FALLBACK_RIDGE·top; L, broken, is
        # where it starts
        _, L = jax.lax.while_loop(
            broken, grow, (_FALLBACK_RIDGE / _FALLBACK_GROWTH * top, L))
        return chol_path(L)

    factored = jnp.all(jnp.isfinite(L))
    return jax.lax.cond(factored, chol_path, ridged_path, L), ~factored


def _psd_solve_device(gram, rhs, lam, refine=2):
    """(gram + lam·I) X = rhs on device: factor, then the shared
    refined solve (see _psd_solve_with_factor)."""
    return _psd_solve_flagged(gram, rhs, lam, refine)[0]


def _psd_solve_flagged(gram, rhs, lam, refine=2):
    """``_psd_solve_device``'s (X, fell back) — the block
    solver's programs return the flag, and a fit reads the flags once,
    after its last block (``_fit_health``)."""
    A = gram + lam * jnp.eye(gram.shape[0], dtype=gram.dtype)
    L = jax.scipy.linalg.cholesky(A, lower=True)
    return _psd_solve_with_factor(A, L, rhs, refine)


@partial(
    jax.jit, static_argnames=("width", "n", "first_pass", "last_pass"),
    donate_argnums=(1,),
)
def _block_step(X, R, Wb, mu, mask, start, lam, *, width: int, n: int,
                first_pass: bool = False, last_pass: bool = False):
    """One whole BCD block update — stats, solve, and residual update —
    as a single XLA program with no host synchronization. The reference's
    executor-GEMM → treeReduce → driver-LAPACK → broadcast → residual
    round trip (BlockLinearMapper.scala:234-240) becomes one dispatch.

    ``first_pass``: on sweep 0 the current block's model is exactly zero
    (fresh fit, or a resumed fit that never completed this block), so the
    old-contribution matmul is skipped — one fewer N·b·k matmul and one
    fewer full read of X per block on the first sweep.

    ``last_pass``: after the final block of the final sweep the residual
    is never read again, so its update (another N·b·k matmul + a full
    residual write) is elided; the returned residual is then stale and
    the caller must not use it.

    Returns (W_b, R, fell back): the last a device bool, true where the
    block's Cholesky broke down and the ridged factor solved it
    (``_fit_health`` reads a fit's flags once, after its last block).
    """
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    mu_b = jax.lax.dynamic_slice_in_dim(mu, start, width)
    if first_pass:
        R_plus = R
    else:
        with jax.named_scope("solver.residual_plus"):
            contrib = _f32_mm(Xb, Wb) - mask[:, None] * _f32_mm(mu_b, Wb)
            R_plus = R + contrib
    gram, rhs = _gram_rhs(Xb, mu_b, R_plus, n)
    with jax.named_scope("solver.solve"):
        Wb_new, fell_back = _psd_solve_flagged(gram, rhs, lam)
    if last_pass:
        return Wb_new, R_plus, fell_back
    with jax.named_scope("solver.residual"):
        contrib_new = (
            _f32_mm(Xb, Wb_new) - mask[:, None] * _f32_mm(mu_b, Wb_new)
        )
        return Wb_new, R_plus - contrib_new, fell_back


def _gram_rhs(Xb, mu_b, R_plus, n):
    """The block's centered Gram and right-hand side (traced inside the
    block programs), under the names a device trace finds them by. Of
    X_bᵀX_b the upper block triangle is multiplied and the lower blocks
    are its transposes (``_sym_gram``); the centering follows the
    assembly."""
    return _centred_gram(Xb, mu_b, n), _rhs(Xb, mu_b, R_plus)


def _centred_gram(Xb, mu_b, n):
    with jax.named_scope("solver.gram"):
        return _sym_gram(Xb) - n * jnp.outer(mu_b, mu_b)


def _rhs(Xb, mu_b, R_plus):
    with jax.named_scope("solver.rhs"):
        return _f32_mm(Xb.T, R_plus) - jnp.outer(
            mu_b, jnp.sum(R_plus, axis=0)
        )


def _block_residual_plus(X, R, Wb, mu, mask, start, width):
    """The block's column slice, its means, and R⁺ = R + X_b W_b (centered):
    the opening of the right-hand side's program."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    mu_b = jax.lax.dynamic_slice_in_dim(mu, start, width)
    with jax.named_scope("solver.residual_plus"):
        contrib = _f32_mm(Xb, Wb) - mask[:, None] * _f32_mm(mu_b, Wb)
        return Xb, mu_b, R + contrib


@partial(jax.jit, static_argnames=("width", "n"))
def _block_stats_gram(X, mu, start, *, width: int, n: int):
    """The block's centred Gram alone, from the RAW (possibly bf16)
    feature matrix and its means — nothing of the residual, so the host
    solve can build it a block ahead of the block's turn (``fit``).

    Centering is algebraic — the centered block is never materialized:
        G_c = X_bᵀX_b − n·μ_bμ_bᵀ
    (pad rows of X are zero, so sums over all rows equal sums over valid
    rows). The contraction over the sharded example axis lowers to
    per-shard MXU matmuls + a psum over the "data" axis; the Gram is built
    from its upper block triangle, square products of column slices of
    ``X`` itself, the lower blocks their transposes (``_sym_gram``).
    ``start`` is traced so every equal-width block shares this
    compilation. The name keeps ``block_stats`` and the first argument
    ``X``: device-time readers find the solver's programs and the Gram's
    products by them."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    mu_b = jax.lax.dynamic_slice_in_dim(mu, start, width)
    return _centred_gram(Xb, mu_b, n)


@partial(jax.jit, static_argnames=("width",), donate_argnums=(1,))
def _block_stats_rhs(X, R, Wb, mu, mask, start, *, width: int):
    """R⁺ and the centered right-hand side rhs_c = X_bᵀR⁺ − μ_b·(1ᵀR⁺),
    every block step of the host solve (the Gram is ``_block_stats_gram``'s,
    on a block's first visit only). The name keeps ``block_stats`` in it:
    device-time readers find the solver's programs by name."""
    Xb, mu_b, R_plus = _block_residual_plus(X, R, Wb, mu, mask, start, width)
    return _rhs(Xb, mu_b, R_plus), R_plus


@partial(jax.jit, static_argnames=("width",), donate_argnums=(1,))
def _residual_update(X, R_plus, Wb_new, mu, mask, start, *, width: int):
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    mu_b = jax.lax.dynamic_slice_in_dim(mu, start, width)
    with jax.named_scope("solver.residual"):
        contrib = (
            _f32_mm(Xb, Wb_new) - mask[:, None] * _f32_mm(mu_b, Wb_new)
        )
        return R_plus - contrib


@jax.jit
def _column_means(X, Y, mask, n):
    """Feature/label means over valid rows, f32 accumulation, one pass.
    Masked: upstream transformers (e.g. ClassLabelIndicators one-hotting)
    may map zero pad rows to nonzero values."""
    m = mask[:, None]
    s1 = jnp.sum(X.astype(jnp.float32) * m, axis=0)
    sY = jnp.sum(Y.astype(jnp.float32) * m, axis=0)
    return s1 / n, sY / n


@jax.jit
def _centered_labels(Y, mu_y, mask):
    return (Y.astype(jnp.float32) - mu_y) * mask[:, None]


@jax.jit
def _prep(X, Y, mask, n):
    """Means + centered residual in ONE dispatch (the Y pass for mu_y
    and the centering write share one program so XLA can fuse them)."""
    with jax.named_scope("solver.prep"):
        mu, mu_y = _column_means.__wrapped__(X, Y, mask, n)
        return mu, mu_y, _centered_labels.__wrapped__(Y, mu_y, mask)


@jax.jit
def _prep_labels(Y, mask, n):
    """Label mean + centered residual only — the host-blocks path has no
    device-resident X to fold into the same program; feature means ride
    each slab's first visit instead (_host_block_step first_pass)."""
    m = mask[:, None]
    mu_y = jnp.sum(Y.astype(jnp.float32) * m, axis=0) / n
    return mu_y, (Y.astype(jnp.float32) - mu_y) * m


@partial(
    jax.jit, static_argnames=("n", "first_pass", "last_pass"),
    donate_argnums=(1,),
)
def _host_block_step(Xb, R, Wb, mu_b, mask, lam, *, n: int,
                     first_pass: bool = False, last_pass: bool = False):
    """One BCD block update on a HOST-STREAMED slab — the same algebra
    as ``_block_step`` operating on a whole (padded_n, w) slab instead
    of a dynamic column slice of a device-resident X (reference:
    BlockLinearMapper.scala:50-73 iterates feature blocks cached in
    cluster RAM; here the slab arrived via an async ``device_put`` the
    caller double-buffers against this program).

    ``first_pass`` additionally computes the block's feature mean from
    the slab (the in-HBM path gets all means from one ``_prep`` pass;
    with X living on host, the mean pass rides the slab's first visit
    — no extra transfer, one extra fused reduction). The fourth result
    is ``_block_step``'s fall-back flag."""
    if first_pass:
        mu_b = (
            jnp.sum(Xb.astype(jnp.float32) * mask[:, None], axis=0) / n
        )
        R_plus = R  # this block's model is exactly zero on sweep 0
    else:
        with jax.named_scope("solver.residual_plus"):
            contrib = _f32_mm(Xb, Wb) - mask[:, None] * _f32_mm(mu_b, Wb)
            R_plus = R + contrib
    gram, rhs = _gram_rhs(Xb, mu_b, R_plus, n)
    with jax.named_scope("solver.solve"):
        Wb_new, fell_back = _psd_solve_flagged(gram, rhs, lam)
    if last_pass:
        return Wb_new, R_plus, mu_b, fell_back
    with jax.named_scope("solver.residual"):
        contrib_new = (
            _f32_mm(Xb, Wb_new) - mask[:, None] * _f32_mm(mu_b, Wb_new)
        )
        return Wb_new, R_plus - contrib_new, mu_b, fell_back


@partial(jax.jit, static_argnames=("n",), donate_argnums=(1,))
def _host_block_rebuild(Xb, R, Wb, mask, *, n: int):
    """Checkpoint-resume residual rebuild for one host slab: recompute
    the block's mean and subtract its restored model's contribution
    (the standard path's ``_residual_update`` + the mean it would have
    had from ``_prep``)."""
    mu_b = jnp.sum(Xb.astype(jnp.float32) * mask[:, None], axis=0) / n
    contrib = _f32_mm(Xb, Wb) - mask[:, None] * _f32_mm(mu_b, Wb)
    return R - contrib, mu_b


def _count_gram_pairs(widths: Sequence[int], times: int = 1) -> None:
    """Count, for ``times`` Grams of each of ``widths``, the column pairs
    ``_sym_gram`` multiplies beside those of the whole Grams (their ratio
    is the share of the full product's MXU work a fit spends)."""
    pairs = [_gram_pairs(w) for w in widths]
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_gram_pairs_computed_total",
        "column pairs multiplied for block Grams (upper block triangle)",
    ).inc(by=times * sum(computed for computed, _ in pairs))
    reg.counter(
        "keystone_solver_gram_pairs_total",
        "column pairs of the block Grams built (width squared a Gram)",
    ).inc(by=times * sum(whole for _, whole in pairs))


def _count_fit() -> Callable[..., None]:
    """Count one fit started; the callable it returns counts one block
    step of that fit on a block of ``width`` columns, and with it either
    the Gram the step built (and its column pairs) or, with
    ``reused_factor``, the kept factor it solved against."""
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_fits_total", "block least-squares fits started"
    ).inc()
    steps = reg.counter(
        "keystone_solver_block_steps_total",
        "block coordinate descent block updates",
    )
    grams = reg.counter(
        "keystone_solver_gram_builds_total",
        "block Gram matrices built on the device",
    )
    reuses = reg.counter(
        "keystone_solver_factor_reuses_total",
        "block steps solved against a factor kept from an earlier sweep",
    )

    def count_step(width: int, reused_factor: bool = False) -> None:
        steps.inc()
        if reused_factor:
            reuses.inc()
        else:
            grams.inc()
            _count_gram_pairs((width,))

    return count_step


@jax.jit
def _fit_health(fell_back, W):
    """(blocks whose Cholesky fell back, whether the model is
    finite) as one int32 pair: a fit's one read after its last block."""
    return jnp.stack([
        jnp.sum(jnp.stack(fell_back).astype(jnp.int32)),
        jnp.all(jnp.isfinite(W)).astype(jnp.int32),
    ])


def _check_fit(fell_back: List[Any], W) -> None:
    """Read a device fit's fall-back flags and the model's finiteness
    once, count them (``keystone_solver_device_block_solves_total``,
    ``keystone_solver_factor_fallbacks_total``), and raise where the model
    is not finite."""
    with span("solver.converged", blocks=len(fell_back)):
        fallbacks, finite = (int(v) for v in np.asarray(
            _fit_health(fell_back, W)))
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_device_block_solves_total",
        "block systems solved on the device (Cholesky, or the ridged "
        "factor where it broke down)",
    ).inc(by=len(fell_back))
    reg.counter(
        "keystone_solver_factor_fallbacks_total",
        "device block solves whose Cholesky factor was not finite, "
        "solved by the factor of A + delta I refined against A (delta from "
        "1e-6 max(diag A), tenfold while the factor breaks down)",
    ).inc(by=fallbacks)
    if not finite:
        raise FloatingPointError(
            "BlockLeastSquaresEstimator: the fitted model is not finite — "
            f"{fallbacks} of {len(fell_back)} block Cholesky factorisations "
            "broke down in float32 and were factored again with a ridge, "
            f"and blocks wider than {_FALLBACK_MAX_WIDTH} columns have no "
            "such fall-back; raise lam or use solve='host'"
        )


def _count_gram_prefetch() -> None:
    get_global_registry().counter(
        "keystone_solver_gram_prefetches_total",
        "block Grams dispatched and copied to the host ahead, before the "
        "previous block's factorisation began",
    ).inc()


def _force_sync(x) -> None:
    """Synchronously force a queued computation by pulling one element
    to host (the repo's timing discipline — bench.py's docstring,
    bin/profile-solvers ``sync()``)."""
    np.asarray(jnp.reshape(x, (-1,))[0])


class _RunAheadLimiter:
    """Caps dispatched-but-unforced pipeline steps at ``window``.

    ``device_put`` allocates its destination buffer at ENQUEUE time, so
    an unthrottled host-blocks loop queues every remaining slab at once
    — peak HBM becomes the sum of ALL slabs instead of the documented
    2-slab bound, and the transfer client retains the matching host
    upload buffers (measured +60 GB transient on the 32 GiB XL fit).
    Forcing the step output from ``window`` steps back keeps at most
    ``window + 1`` slabs in flight while H2D still rides under compute;
    the forced sync costs one host round trip per step, noise against
    the slab transfers the host path exists for."""

    def __init__(self, window: int = 2):
        self._window = window
        self._q: deque = deque()

    def add(self, step_output) -> None:
        self._q.append(step_output)
        if len(self._q) > self._window:
            _force_sync(self._q.popleft())


def _host_blocks_probe(blocks: Sequence[np.ndarray], Y) -> str:
    """Cheap order-sensitive digest of a host-blocks dataset for
    checkpoint fingerprints — strided row/column samples per block (a
    full ``data_probe`` scan of a host-RAM-scale X would read the whole
    array just to stamp a snapshot)."""
    parts = []
    for b in blocks:
        rows = [0, b.shape[0] // 3, (2 * b.shape[0]) // 3, b.shape[0] - 1]
        cols = slice(0, min(8, b.shape[1]))
        sample = np.asarray(b[rows, cols], np.float64)
        parts.append(
            f"{b.shape}:{b.dtype}:"
            + ",".join(f"{v:.6e}" for v in sample.ravel())
        )
    ysum = float(np.asarray(jnp.sum(Y.astype(jnp.float32))))
    return ";".join(parts) + f"|Y={ysum:.6e}"


@dataclasses.dataclass(eq=False)
class BlockLinearMapper(Transformer):
    """Applies the block-solved linear model. Weights are stored as one
    (D, k) matrix (the concatenation of the reference's per-block models,
    BlockLinearMapper.scala:22) so test-time apply is one MXU matmul."""

    W: Any  # (D, k)
    block_size: int
    feature_mean: Optional[Any] = None  # (D,)
    label_mean: Optional[Any] = None  # (k,)
    explicit_intercept: Optional[Any] = None  # (k,); weighted solver sets it
    solver_info: Optional[dict] = None  # lazy solver diagnostics (e.g.
    # the weighted solver's PCG exit residual); values may be device
    # scalars — reading them forces a host sync

    @property
    def intercept(self):
        if self.explicit_intercept is not None:
            return self.explicit_intercept
        if self.label_mean is None:
            return None
        if self.feature_mean is None:
            return self.label_mean
        return self.label_mean - _f32_mm(self.feature_mean, self.W)

    def apply(self, x):
        out = _f32_mm(x, self.W)
        icpt = self.intercept
        return out if icpt is None else out + icpt

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_host:
            return self._apply_host_blocks(ds)
        out = _f32_mm(ds.padded(), self.W)
        icpt = self.intercept
        if icpt is not None:
            out = (out + icpt) * ds.mask()[:, None]
        return Dataset.from_array(out, n=ds.n)

    def _apply_host_blocks(self, ds: Dataset) -> Dataset:
        """Predict from a host-blocked feature matrix: stream each slab
        (double-buffered, like the fit) and accumulate X_b W_b on
        device — HBM holds 2 slabs + the (n, k) output, never X."""
        blocks = ds.host_blocks
        out = None
        s = 0
        limiter = _RunAheadLimiter()
        nxt = jax.device_put(blocks[0])
        for i, b in enumerate(blocks):
            cur = nxt
            if i + 1 < len(blocks):
                nxt = jax.device_put(blocks[i + 1])
            w = b.shape[1]
            part = _f32_mm(cur, self.W[s : s + w])
            out = part if out is None else out + part
            limiter.add(out)
            s += w
            del cur
        if s != self.W.shape[0]:
            raise ValueError(
                f"host blocks cover {s} features but the model has "
                f"{self.W.shape[0]}"
            )
        icpt = self.intercept
        if icpt is not None:
            out = (out + icpt) * ds.mask()[:, None]
        return Dataset.from_array(out, n=ds.n)

    def apply_and_evaluate(
        self, ds: Dataset, evaluator: Callable[[jnp.ndarray], None]
    ) -> None:
        """Stream per-block partial prediction sums to ``evaluator`` after
        each block (reference: BlockLinearMapper.applyAndEvaluate:95-137) —
        lets callers watch train error improve block by block."""
        X = ds.padded()
        D = X.shape[1]
        icpt = self.intercept
        acc = jnp.zeros((X.shape[0], self.W.shape[1]), X.dtype)
        for start in range(0, D, self.block_size):
            end = min(start + self.block_size, D)
            acc = acc + _f32_mm(X[:, start:end], self.W[start:end])
            out = acc if icpt is None else (acc + icpt) * ds.mask()[:, None]
            evaluator(out)

    @property
    def weight(self) -> int:
        return 2


@dataclasses.dataclass(eq=False)
class BlockLeastSquaresEstimator(LabelEstimator):
    """Gauss-Seidel block coordinate descent for L2-regularized least
    squares (reference: BlockLinearMapper.scala:199-283). ``num_iter``
    sweeps over ``ceil(D / block_size)`` blocks; one sweep reproduces the
    reference's single-pass path (solveOnePassL2)."""

    block_size: int
    num_iter: int = 1
    lam: float = 0.0
    num_features: Optional[int] = None  # pad/truncate hint, parity only
    solve: str = "device"  # "device" (f32 chol + refinement, zero host
    # syncs — the fast path) | "host" (f64 LAPACK per block, for
    # pathologically conditioned systems; costs a dispatch round-trip
    # per block)
    checkpoint_path: Optional[str] = None  # periodic loop-state snapshot;
    # a re-run with the same path resumes at the last completed block
    # (reference: lineage checkpoint every 25 blocks,
    # KernelRidgeRegression.scala:200-210 — see utils/checkpoint.py)
    checkpoint_every: int = 25
    block_callback: Optional[Callable[[int], None]] = None  # called with a
    # running count after each completed block update (per-block progress
    # logging in the reference driver loop)

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        if self.solve not in ("device", "host"):
            raise ValueError(f"solve must be 'device' or 'host', got {self.solve!r}")
        if data.is_host:
            return self._fit_host_blocks(data, labels)
        # Mean-centering of features and labels (reference fits
        # StandardScaler(normalizeStdDev=false) per block + labels:
        # BlockLinearMapper.scala:209-215; full-width centering is
        # mathematically identical) happens algebraically inside the Gram
        # math — X is never copied, so bf16 feature matrices of HBM scale
        # pass through untouched.
        count_step = _count_fit()
        with span("solver.prep"):
            data = data.to_array_mode()
            labels = labels.to_array_mode()
            X = data.padded()
            Y = labels.padded()
            n = data.n
            D = X.shape[1]
            k = Y.shape[1]
            mask = data.mask()
            mu, mu_y, R = _prep(X, Y, mask, n)

        blocks = [
            (s, min(s + self.block_size, D) - s)
            for s in range(0, D, self.block_size)
        ]
        Wb = {s: jnp.zeros((w, k), jnp.float32) for s, w in blocks}

        ckpt = None
        start_it, start_pos = 0, 0
        if self.checkpoint_path is not None:
            # stamp config + problem shape + a cheap data probe so a
            # snapshot from a different fit is discarded, not resumed
            fp = (
                f"bls bs={self.block_size} it={self.num_iter} "
                f"lam={self.lam} solve={self.solve} n={n} D={D} k={k} "
                f"probe={data_probe(X, Y)}"
            )
            ckpt = LoopCheckpointer(self.checkpoint_path,
                                    self.checkpoint_every, fingerprint=fp)
            state = ckpt.load()
            if state is not None:
                start_it = int(state["it"])
                start_pos = int(state["pos"])
                for s, w in blocks:
                    if not np.any(state[f"Wb_{s}"]):
                        continue  # untouched block: zero contribution
                    Wb[s] = jnp.asarray(state[f"Wb_{s}"], jnp.float32)
                    # Rebuild the residual from the compact snapshot —
                    # the lineage-truncation analogue: recompute the big
                    # intermediate instead of persisting it.
                    R = _residual_update(X, R, Wb[s], mu, mask, s, width=w)

        def snapshot(next_it: int, next_pos: int):
            st = {"it": next_it, "pos": next_pos}
            for s, _ in blocks:
                st[f"Wb_{s}"] = np.asarray(Wb[s])
            return st

        # solve="host": each block's float64 factor, by start column,
        # from the block's first visit in THIS call (a resumed fit enters
        # a later sweep with none) until the call returns. b²·8 bytes of
        # host memory a block; the next fit builds its own.
        factors: Dict[int, HostFactor] = {}
        steps = list(two_level_schedule(
            self.num_iter, len(blocks), (start_it, start_pos)
        ))
        # solve="host": (start, read-back handle) of the Gram of the next
        # block to be factored, dispatched and read back ahead of the
        # block's turn (G needs X and μ only), under the previous block's
        # factorisation. A block's first visits in a call are its first
        # len(blocks) steps, one after another, so the next step's block
        # is known. At most one Gram is on the device: the current one is
        # read back, and so deleted, before the next is dispatched.
        def gram_ahead(pos: int):
            s, w = blocks[pos]
            with span("solver.gram_ahead"):
                return s, read_back_ahead(
                    _block_stats_gram(X, mu, s, width=w, n=n)
                )

        ahead = (
            gram_ahead(steps[0][1]) if self.solve == "host" and steps
            else None
        )
        done = 0
        fell_back: List[Any] = []  # device solve: a flag a block step
        for j, (it, pos, nxt) in enumerate(steps):
            s, w = blocks[pos]
            kept = factors.get(s)
            if self.solve == "device":
                # whole block update in one dispatch; the entire fit
                # stays in the async stream — no host sync until its
                # last block's flags are read (_check_fit). On sweep 0
                # this block's model is zero in every path (including
                # checkpoint resume: only never-completed blocks are
                # revisited in sweep 0), so the old-contribution matmul
                # is elided.
                with span("solver.block_step"):
                    Wb[s], R, flag = _block_step(
                        X, R, Wb[s], mu, mask, s, self.lam,
                        width=w, n=n, first_pass=(it == 0),
                        last_pass=(
                            it == self.num_iter - 1
                            and pos == len(blocks) - 1
                        ),
                    )
                fell_back.append(flag)
            else:
                # (b,b) solve on host in f64 (reference: driver-side
                # NormalEquations solve) — see hostsolve.py, which has
                # the solver.readback and solver.host_solve spans.
                with span("solver.block_stats"):
                    rhs, R_plus = _block_stats_rhs(
                        X, R, Wb[s], mu, mask, s, width=w
                    )
                if kept is None:
                    ahead_s, gram = ahead
                    if ahead_s != s:
                        raise AssertionError(
                            f"Gram of block {ahead_s} read back ahead for {s}"
                        )
                    # the Gram is off the device once it is read back
                    G, R_host = read_back(gram, rhs)
                    ahead = gram = None
                    if j + 1 < len(steps):
                        s_next = blocks[steps[j + 1][1]][0]
                        if s_next != s and s_next not in factors:
                            ahead = gram_ahead(steps[j + 1][1])
                            _count_gram_prefetch()
                    W_host, factor = factor_solve_host(G, R_host, self.lam)
                    if self.num_iter > 1:  # one sweep never comes back
                        factors[s] = factor
                else:
                    W_host = psd_solve_factored_host(kept, rhs)
                with span("solver.upload"):
                    Wb[s] = jnp.asarray(W_host)
                with span("solver.residual_update"):
                    R = _residual_update(
                        X, R_plus, Wb[s], mu, mask, s, width=w
                    )
            count_step(w, reused_factor=kept is not None)
            done += 1
            if ckpt is not None:
                ckpt.tick(lambda: snapshot(*nxt))
            if self.block_callback is not None:
                self.block_callback(done)
        if ckpt is not None:
            ckpt.clear()  # fit completed; stale state must not leak into
            # a later fit at the same path
        W = jnp.concatenate([Wb[s] for s, _ in blocks], axis=0)
        if fell_back:
            _check_fit(fell_back, W)
        return BlockLinearMapper(
            W,
            self.block_size,
            feature_mean=mu,
            label_mean=mu_y,
        )

    def _fit_host_blocks(self, data: Dataset, labels: Dataset
                         ) -> BlockLinearMapper:
        """Out-of-aggregate-HBM fit: X lives in host RAM as column
        blocks (Dataset.from_host_blocks — the cluster-RAM feature
        cache of BlockLinearMapper.scala:50-73 / the 75%-of-memory
        budget of AutoCacheRule.scala:559-602); each (padded_n, w) slab
        is transferred per pass with the NEXT slab's async ``device_put``
        double-buffered against the current block's Gram/solve/update
        program, so H2D rides under compute. HBM holds 2 slabs + the
        residual, independent of D — the fit is bounded by host RAM.

        The data-blocking ignores ``self.block_size``: the dataset's own
        block layout IS the coordinate-descent blocking (matching the
        reference, where the Seq of feature RDDs defines the blocks)."""
        count_step = _count_fit()
        blocks = data.host_blocks
        widths = data.block_widths
        n = data.n
        pn = data.padded_n
        mask = data.mask()
        lab = labels.to_array_mode()
        if lab.padded_n != pn:
            lab = lab._pad_to(pn)
        Y = lab.padded()
        mu_y, R = _prep_labels(Y, mask, n)
        k = Y.shape[1]
        nb = len(blocks)
        Wb: List[Any] = [jnp.zeros((w, k), jnp.float32) for w in widths]
        mu_bs: List[Any] = [None] * nb

        from keystone_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.current_mesh()
        nshards = mesh.shape[mesh_lib.DATA_AXIS]
        # rows over the mesh's data axis when they divide evenly (the
        # multichip layout); otherwise default single-device placement
        sharding = (
            mesh_lib.data_sharding(mesh) if pn % nshards == 0 else None
        )

        def put(bi: int):
            # async H2D; jax returns immediately and the copy streams
            # while the previous block's program occupies the chip
            if sharding is not None:
                return jax.device_put(blocks[bi], sharding)
            return jax.device_put(blocks[bi])

        ckpt = None
        start_it, start_pos = 0, 0
        if self.checkpoint_path is not None:
            fp = (
                f"bls-host nb={nb} widths={widths} it={self.num_iter} "
                f"lam={self.lam} n={n} k={k} "
                f"probe={_host_blocks_probe(blocks, Y)}"
            )
            ckpt = LoopCheckpointer(self.checkpoint_path,
                                    self.checkpoint_every, fingerprint=fp)
            state = ckpt.load()
            if state is not None:
                start_it = int(state["it"])
                start_pos = int(state["pos"])
                for bi in range(nb):
                    if not np.any(state[f"Wb_{bi}"]):
                        continue
                    Wb[bi] = jnp.asarray(state[f"Wb_{bi}"], jnp.float32)
                    R, mu_bs[bi] = _host_block_rebuild(
                        put(bi), R, Wb[bi], mask, n=n
                    )
                    # serialize rebuild transfers (bounded HBM; resume
                    # is rare so the lost overlap is irrelevant)
                    _force_sync(mu_bs[bi])

        def snapshot(next_it: int, next_pos: int):
            st = {"it": next_it, "pos": next_pos}
            for bi in range(nb):
                st[f"Wb_{bi}"] = np.asarray(Wb[bi])
            return st

        schedule = list(two_level_schedule(
            self.num_iter, nb, (start_it, start_pos)
        ))
        done = 0
        fell_back: List[Any] = []
        nxt = put(schedule[0][1]) if schedule else None
        limiter = _RunAheadLimiter()
        for j, (it, bi, nxt_state) in enumerate(schedule):
            Xb = nxt
            if j + 1 < len(schedule):
                nxt = put(schedule[j + 1][1])  # prefetch: double buffer
            first = it == 0
            mu_arg = (
                mu_bs[bi]
                if mu_bs[bi] is not None
                else jnp.zeros((widths[bi],), jnp.float32)
            )
            with span("solver.block_step"):
                Wb[bi], R, mu_bs[bi], flag = _host_block_step(
                    Xb, R, Wb[bi], mu_arg, mask, self.lam, n=n,
                    first_pass=first,
                    last_pass=(
                        it == self.num_iter - 1 and bi == nb - 1
                    ),
                )
            fell_back.append(flag)
            count_step(widths[bi])
            del Xb  # release this slab's HBM as soon as XLA is done
            limiter.add(Wb[bi])
            done += 1
            if ckpt is not None:
                ckpt.tick(lambda: snapshot(*nxt_state))
            if self.block_callback is not None:
                self.block_callback(done)
        if ckpt is not None:
            ckpt.clear()
        W = jnp.concatenate([jnp.asarray(w) for w in Wb], axis=0)
        if fell_back:
            _check_fit(fell_back, W)
        mu = jnp.concatenate(mu_bs, axis=0)
        return BlockLinearMapper(
            W,
            max(widths),
            feature_mean=mu,
            label_mean=mu_y,
        )

    @property
    def weight(self) -> int:
        # reference: BlockLinearMapper.scala:204
        return 3 * self.num_iter + 1

    def cost(
        self,
        n: int,
        d: int,
        k: int,
        sparsity: float,
        num_machines: int,
        cpu_weight: float,
        mem_weight: float,
        network_weight: float,
    ) -> float:
        """Analytic flops/mem/net cost (reference:
        BlockLinearMapper.scala:268-282)."""
        import math

        flops = n * float(d) * (self.block_size + k) / num_machines
        bytes_scanned = n * float(d) / num_machines + float(d) * k
        network = (
            2.0
            * (float(d) * (self.block_size + k))
            * max(math.log2(num_machines), 1.0)
        )
        return self.num_iter * (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )
