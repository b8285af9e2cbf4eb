"""Weighted block coordinate descent for per-class mixture-weighted least
squares — the ImageNet flagship solver.

Reference: nodes/learning/BlockWeightedLeastSquares.scala:36,102-320.
The objective re-weights each class's examples by ``mixture_weight`` w:
per class c the solve uses joint statistics
    jointXTX_c = (1−w)·popCov + w·classCov_c + w(1−w)·δ_c δ_cᵀ
    jointXTR_c = (1−w)·popXTR[:,c] + w·classXTR_c − jointMean_c·mmw_c
with δ_c = classMean_c − popMean and
mmw_c = (1−w)·residualMean_c + w·mean(resLocal_c).

The reference requires a partition-per-class layout (groupByClasses with
HashPartitioner(nClasses), :332-369) so per-class statistics are
partition-local. TPU-native equivalent: sort rows by class ONCE into a
(C, m, ·) class-grouped gather index (classes padded to the max class
size with zero-weight rows) — the EP-style grouping of SURVEY §2.10 —
then per-class covariances are one batched einsum over class chunks and
the per-class (b, b) solves are one batched Cholesky, all on device.
Total flops match the reference (Σ_c n_c·b² = n·b²); no shuffle, no
driver round trip, no distributed System.gc(). That is ``solve="chol"``.

``solve="pcg"`` (what ``auto`` takes at wide blocks) never forms a class
covariance: all C systems share one matrix-free preconditioned CG
(``_pcg_block_core``). Its statistics are one population Gram X_bᵀX_b,
of which the upper block triangle is multiplied and the lower blocks are
its transposes (``block_ls._sym_gram``; the chol path's ``_pop_stats``
builds the same Gram the same way), XᵀR on the original rows, and two
class-restricted moments (class sums, own-residual sums) — on a first
block step from single-label ±1 indicator labels on sorted rows, XᵀR and
the own-residual sums follow from the class sums; those moments
and the matvec need each row with its own class only, and read one of
two row layouts, chosen a fit from what the estimator can see
(``_sorted_layout``): ``sorted`` — the block's rows gathered once a
block step into class order, tiles of consecutive rows against a window
of 128 classes — where the copy fits the device beside X and the class
counts keep every tile inside one window, and ``original`` — one-hot
GEMMs over all C classes, no copy, C/128 times the operations —
elsewhere (sharded rows, host-RAM slabs, tight memory, classes so small
that 16,384 sorted rows hold more than 128 of them).

Observability: host spans ``solver.wls.prep`` (array mode, padding, the
labels' cast), ``solver.wls.layout`` (pcg: the choice of the matvec's
row layout, with its read of the class counts), ``solver.wls.dispatch``
(the fit's device programs enqueued; on the chol path its host loop
too) and ``solver.wls.converged`` (the read of the CG exit residual
that ``convergence_check`` waits on); on the device ``jax.named_scope``
names ``wls.setup`` / ``wls.stats`` / ``wls.precond`` / ``wls.sort`` /
``wls.cg`` / ``wls.update``; counters ``keystone_solver_wls_fits_total``,
``keystone_solver_wls_path_total{solve,layout}`` (the path ``auto``
took), ``keystone_solver_wls_sorted_fits_total`` (fits whose matvec ran
on sorted rows), ``keystone_solver_wls_sorted_stats_fits_total`` (fits
whose class-restricted statistics read sorted rows),
``keystone_solver_wls_label_moments_fits_total`` (fits whose first block
step took its moments from the class sums),
``keystone_solver_wls_pcg_iterations_total`` (the
iterations a fit reports, added where ``convergence_check`` reads them
anyway) and block_ls's ``keystone_solver_gram_pairs_computed_total`` /
``_gram_pairs_total`` (the column pairs a fit's population Grams
multiply, beside those of the whole Grams).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.learning.block_ls import (
    BlockLinearMapper,
    _count_gram_pairs,
    _f32_mm,
    _sym_gram,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import LabelEstimator


@partial(jax.jit, static_argnames=("G", "m", "width"))
def _class_chunk_stats(Xg, R, wt, counts, class_ids, c0, start,
                       *, G, m, width):
    """Per-class covariance/XTR for one chunk of classes, reading the
    CLASS-GROUPED feature layout.

    Xg: (C·m, D) features grouped by class (class c occupies rows
    [c·m, (c+1)·m), padded slots zeroed); R: (C·m, C) residual in the
    same row order; wt: (C, m) 0/1 validity; counts: (C,);
    class_ids: (G,) class index of each chunk row; c0: first class of
    the chunk. Returns classCov (G, b, b), classMean (G, b),
    classXTR (G, b), resLocalMean (G,).

    Grouping means every read here is a contiguous dynamic-slice — the
    per-chunk row gathers this replaced were re-gathering the whole
    dataset once per block (TPU row-gather is far below stream
    bandwidth; measured 10 TFLOP/s on the r3 bench before this).
    """
    D = Xg.shape[1]
    C = R.shape[1]
    Xc = jax.lax.dynamic_slice(
        Xg.reshape(-1, m, D), (c0, 0, start), (G, m, width)
    )  # (G, m, b) — padded slots are already zero
    wc = jax.lax.dynamic_slice(wt, (c0, 0), (G, m))
    inv = 1.0 / jax.lax.dynamic_slice(counts, (c0,), (G,))
    # resLocal_c = R[rows of c, c] — a (G, m, C) contiguous slice then a
    # per-class column pick
    Rc = jax.lax.dynamic_slice(
        R.reshape(-1, m, C), (c0, 0, 0), (G, m, C)
    )
    r_g = (
        jnp.take_along_axis(Rc, class_ids[:, None, None], axis=2)[..., 0]
        * wc
    )  # (G, m)
    class_mean, class_xtr, res_local_mean = _chunk_moments(Xc, r_g, inv)
    # HIGHEST for f32 inputs: the centered covariance cancels mean^2-
    # scale terms; TPU DEFAULT precision would truncate f32 operands to
    # bf16 passes (block_ls._f32_mm documents the measured failure).
    # bf16 inputs ride the native bf16xbf16->f32 MXU path.
    hp = (
        jax.lax.Precision.HIGHEST
        if Xc.dtype == jnp.float32 else None
    )
    class_cov = (
        jnp.einsum("gmb,gmc->gbc", Xc, Xc,
                   preferred_element_type=jnp.float32, precision=hp)
        * inv[:, None, None]
        - class_mean[:, :, None] * class_mean[:, None, :]
    )
    return class_cov, class_mean, class_xtr, res_local_mean


@jax.jit
def _group_rows(X, Y, idx, wt, joint_label_mean):
    """ONE gather into the class-grouped layout: Xg (C·m, D) with padded
    slots zeroed, and the initial residual R (C·m, C) = (Y − jlm)·wt in
    the same row order. This is the only non-contiguous memory access of
    the whole fit."""
    flat = idx.reshape(-1)
    w = wt.reshape(-1)
    Xg = X[flat] * w[:, None].astype(X.dtype)
    R = (Y[flat] - joint_label_mean[None, :]) * w[:, None]
    return Xg, R


@partial(jax.jit, static_argnames=("width", "n"))
def _pop_stats(X, R, mask, start, *, width, n):
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    pop_mean = jnp.einsum("nb->b", Xb * mask[:, None]) / n
    pop_cov = _sym_gram(Xb) / n - jnp.outer(pop_mean, pop_mean)
    pop_xtr = _f32_mm(Xb.T, R) / n
    return pop_mean, pop_cov, pop_xtr


@jax.jit
def _batched_psd_solve(A, B, lam):
    """Solve (A_g + λI) x_g = B_g batched, Jacobi-preconditioned f32
    Cholesky (systems are covariance-normalized, O(1) scale)."""
    b = A.shape[-1]
    A = A + lam * jnp.eye(b, dtype=A.dtype)[None]
    d = jnp.sqrt(jnp.maximum(jnp.diagonal(A, axis1=1, axis2=2), 1e-12))
    scale = d[:, :, None] * d[:, None, :]
    An = A / scale
    L = jnp.linalg.cholesky(An)
    Bn = B / d[:, :, None] if B.ndim == 3 else (B / d)[:, :, None]
    y = jax.scipy.linalg.solve_triangular(L, Bn, lower=True)
    x = jax.scipy.linalg.solve_triangular(
        jnp.swapaxes(L, 1, 2), y, lower=False
    )
    return x[:, :, 0] / d if B.ndim == 2 else x / d[:, :, None]


@partial(jax.jit, static_argnames=("width",), donate_argnums=(1,))
def _apply_delta(X, R, delta, start, *, width):
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    return R - _f32_mm(Xb, delta)


def _device_memory_limit() -> int:
    """Device memory size in bytes (budget input for the chol-path
    grouped-copy decision), from the allocator's ``bytes_limit``. CPU
    backends report no device stats and budget from HOST RAM instead —
    a flat figure there could drive the grouped-layout decision to OOM
    a small CPU host (ADVICE r4), and ``layout='gathered'`` stays the
    manual escape hatch. An accelerator that reports no ``bytes_limit``
    is an error: guessing a size for a device we cannot see into would
    hide it. The stats probe itself is the shared None-guarded helper
    in ``observability/device.py`` (one code path with auto_cache and
    the memory telemetry gauges)."""
    from keystone_tpu.observability.device import (
        device_memory_stats,
        host_memory_stats,
    )

    dev = jax.devices()[0]
    stats = device_memory_stats(dev)
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        host = host_memory_stats()
        if host and "bytes_limit" in host and "bytes_in_use" in host:
            # budget a quarter of available RAM: the layout copy
            # competes with the data itself + the OS
            return (host["bytes_limit"] - host["bytes_in_use"]) // 4
        return 4 * 1024**3
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no "
        "memory_stats()['bytes_limit']; cannot budget the grouped "
        "layout — pass layout='grouped' or 'gathered' explicitly"
    )


@jax.jit
def _precond_inverse(pop_cov, w, lam):
    """EXPLICIT inverse of the shared CG preconditioner M = (1−w)·popCov
    + (λ+ε·scale)·I, via one Cholesky + cho_solve against I. Keeping
    the factor instead would cost two triangular solves per CG
    iteration — once the single largest cost of the flagship fit —
    where the explicit inverse applies as one small GEMM. Inverse
    rounding (κ(M)·ε_f32) only
    perturbs the preconditioner, never the solution; symmetrization
    keeps PCG's SPD contract.

    The ε jitter guards rank-deficient population covariances (λ may be
    0); it biases only the preconditioner, never the solution."""
    b = pop_cov.shape[0]
    eps = 1e-6 * jnp.maximum(jnp.trace(pop_cov) / b, 1e-12)
    M = (1.0 - w) * pop_cov + (lam + eps) * jnp.eye(b, dtype=pop_cov.dtype)
    L = jnp.linalg.cholesky(M)
    Minv = jax.scipy.linalg.cho_solve(
        (L, True), jnp.eye(b, dtype=pop_cov.dtype)
    )
    return (Minv + Minv.T) * 0.5


def _chunk_moments(Xc, r_g, inv):
    """Shared per-chunk moments: classMean (G, b), classXTR (G, b),
    resLocalMean (G,). Invariant: padded slots of Xc and r_g are ZEROED
    by the caller (grouping or gather wrappers), so plain sums are
    per-class sums. Precision policy: f32 accumulation everywhere; the
    r_g contraction is always f32 (residual) -> HIGHEST."""
    f32 = jnp.float32
    cmean = (
        jnp.einsum("gmb->gb", Xc, preferred_element_type=f32)
        * inv[:, None]
    )
    cxtr = (
        jnp.einsum("gmb,gm->gb", Xc, r_g,
                   preferred_element_type=f32,
                   precision=jax.lax.Precision.HIGHEST)
        * inv[:, None]
    )
    rlm = jnp.einsum("gm->g", r_g) * inv
    return cmean, cxtr, rlm


def _limb3(a, axis):
    """Split an f32 array into 3 bf16 limbs concatenated along ``axis``
    (hi+mid+lo carries ~24 mantissa bits, relative error ~2^-24). A
    contraction of bf16 data against the concatenated limbs is ONE
    native-MXU GEMM that reads the big operand once and recovers f32
    accuracy by summing the three output slabs — versus XLA's 6-pass
    HIGHEST decomposition for f32 operands (bf16 x bf16 products are
    exact in the MXU's f32 accumulator, so only the f32 side needs
    splitting)."""
    hi = a.astype(jnp.bfloat16)
    r1 = a - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=axis)


def _sum3(t, axis):
    """Sum the 3 limb slabs of a contraction against ``_limb3`` output."""
    k = t.shape[axis] // 3
    s0 = jax.lax.slice_in_dim(t, 0, k, axis=axis)
    s1 = jax.lax.slice_in_dim(t, k, 2 * k, axis=axis)
    s2 = jax.lax.slice_in_dim(t, 2 * k, 3 * k, axis=axis)
    return s0 + s1 + s2


def _dot00(a, b):
    """dot_general contracting both leading axes (no transpose relayout),
    f32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot11(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot10(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _class_sorted_rows(P, tile):
    """The rows' order by class for the sorted-rows matvec, from the
    one-hot membership P (n, C): ``order`` (a stable argsort of each
    row's class; rows of no class — pad rows, rows without a positive
    label — last) and ``kcls``, the class of each sorted row (C for a
    row of no class), both (tiles, tile): padded to a whole number of
    tiles with rows of no class. The order follows from the labels
    alone: one sort a fit serves every block step."""
    n_rows, C = P.shape
    col = jax.lax.broadcasted_iota(jnp.int32, P.shape, 1)
    cls = jnp.min(jnp.where(P > 0, col, C), axis=1)  # P is one-hot
    order = jnp.argsort(cls, stable=True).astype(jnp.int32)
    pad = -n_rows % tile
    return (
        jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
        .reshape(-1, tile),
        jnp.concatenate([cls[order], jnp.full((pad,), C, jnp.int32)])
        .reshape(-1, tile),
    )


def _own_class_entries(R, order, kcls):
    """R[order, kcls] (tiles, tile): each sorted row's entry of R (n, C)
    in its own class, taken by index; 0 for a row of no class and for
    the pad rows that fill the last tile."""
    C = R.shape[1]
    return jnp.where(
        kcls < C,
        R.at[order, jnp.minimum(kcls, C - 1)].get(mode="promise_in_bounds"),
        0.0,
    )


def _sorted_windows(Xs, kcls, C, window):
    """The window scheme of the products on class-sorted rows. ``Xs``
    (tiles, tile, b) holds the block's rows in class order and ``kcls``
    (tiles, tile) each row's class (C: none). Tile i's rows lie in
    classes [lo_i, lo_i + window) (``_sorted_layout`` holds the caller
    to that), so a row meets ``window`` classes, not C. Returns

    - ``wrows`` (tiles, window): the classes of each tile's window;
    - ``own()``: (tiles, tile, window), each row's own class marked in
      its tile's window (nothing for a row of no class);
    - ``bdot(spec, a, c)``: a product batched over tiles, f32 accumulated
      — f32 data at HIGHEST, bf16 data at the native pass, whose caller
      hands it the three limbs of an f32 side;
    - ``add(S)``: S (tiles, window, …) added into rows [lo_i, lo_i +
      window) of a (C, …) result."""
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    bf16_data = Xs.dtype == jnp.bfloat16
    lo = kcls[:, 0]  # sorted: a tile's first row has its lowest class
    # a row of no class matches no column of the window
    kloc = jnp.where(kcls < C, kcls - lo[:, None], -1)
    # the result is padded with ``window`` rows, so a window that starts
    # at the last classes (or at C: a tile of pad rows) stays inside it
    wrows = lo[:, None] + jnp.arange(window, dtype=jnp.int32)

    def own():
        return kloc[:, :, None] == jnp.arange(window, dtype=jnp.int32)

    def bdot(spec, a, c):
        return jnp.einsum(spec, a, c, preferred_element_type=f32,
                          precision=None if bf16_data else hp)

    def add(S):
        out = jnp.zeros((C + window,) + S.shape[2:], f32).at[wrows].add(
            S, mode="promise_in_bounds"
        )
        return out[:C]

    return wrows, own, bdot, add


def _sorted_class_products(Xs, kcls, C, window):
    """The CG matvec's two data-sized products on class-sorted rows
    (``_sorted_windows``). Returns v (C, b) -> Σ_{i in c} x_i (x_i·v_c),
    (C, b): T_i = X_i·V_iᵀ against the window's vectors (tile, window),
    all but each row's own-class entry (z_i) zeroed by a one-hot local
    to the window, then X_iᵀ(onehot_i ⊙ z_i) (window, b), added into
    rows [lo_i, lo_i + window) of the result. Precision as on the
    original rows: f32 data at HIGHEST, bf16 data against the three
    limbs of the f32 side."""
    f32 = jnp.float32
    b = Xs.shape[2]
    bf16_data = Xs.dtype == jnp.bfloat16
    wrows, own, bdot, add = _sorted_windows(Xs, kcls, C, window)

    def products(v):
        vp = jnp.concatenate([v, jnp.zeros((window, b), f32)])
        Vw = vp.at[wrows].get(mode="promise_in_bounds")  # (tiles, K, b)
        if bf16_data:
            T = _sum3(bdot("itb,ikb->itk", Xs, _limb3(Vw, 1)), axis=2)
        else:
            T = bdot("itb,ikb->itk", Xs, Vw)
        # onehot ⊙ z without z: a row's one own-class entry of T is z_i
        oz = jnp.where(own(), T, 0.0)  # (tiles, tile, K)
        if bf16_data:
            S = _sum3(bdot("itb,itk->ikb", Xs, _limb3(oz, 2)), axis=1)
        else:
            S = bdot("itb,itk->ikb", Xs, oz)
        return add(S)

    return products


def _sorted_class_moments(Xs, kcls, r, C, window):
    """The statistics' class-restricted moments on class-sorted rows
    (``_sorted_windows``): the class sums Σ_{i in c} x_i and the
    own-residual sums Σ_{i in c} r_i x_i, (C, b) each, and Σ_{i in c}
    r_i (C,), where ``r`` (tiles, tile) is each sorted row's residual in
    its own class (0 for a row of no class). Two products a tile,
    X_iᵀ onehot_i and X_iᵀ(onehot_i ⊙ r_i), each against (tile, window)
    columns: 2·n·b·window operations a product where the one-hot
    products over all classes spend 2·n·b·C, and one (tiles, tile,
    window) operand held at a time, as the CG's products hold. f32 data
    at HIGHEST; bf16 data against the one-hot as it is (0/1 is exact in
    bf16) and the three limbs of onehot ⊙ r. With ``r`` None, the class
    sums alone: one product."""
    _, own, bdot, add = _sorted_windows(Xs, kcls, C, window)
    onehot = own()
    # 0/1 is exact in either dtype of the data
    sums = add(bdot("itb,itk->ikb", Xs, onehot.astype(Xs.dtype)))
    if r is None:
        return sums
    orr = jnp.where(onehot, r[:, :, None], 0.0)  # (tiles, tile, window)
    if Xs.dtype == jnp.bfloat16:
        rsums = _sum3(bdot("itb,itk->ikb", Xs, _limb3(orr, 2)), axis=1)
    else:
        rsums = bdot("itb,itk->ikb", Xs, orr)
    return sums, add(rsums), add(jnp.einsum("itk->ik", orr))


def _pcg_block_core(X, R, P, Wb, inv_counts, valid, start, w, lam,
                    sort=None, labels=None, *, width, n, max_iters=96,
                    tol=1e-6, sort_window=0):
    """One whole weighted-BCD block update for ALL classes at once, as a
    single device program: population stats, shared-preconditioner
    inverse, batched matrix-free PCG over the C per-class systems, and
    the residual update. The update and the population statistics read
    the ORIGINAL (ungrouped) rows; the class-restricted statistics and
    the CG matvec read them too, or a class-sorted copy of the block
    (``sort``, below). ``labels`` = (class counts, jlm, row mask), with
    ``sort`` only, marks the first block step of a fit whose labels are
    single-label ±1 indicators (below).

    This replaced a design of class-grouped gathers and 8 class-chunks,
    each its own CG with triangular-solve preconditioning, whose
    chunked TRSMs were the largest single cost of the flagship fit.
    Here:

    - the statistics' per-class contractions are products with the
      class membership: with P (n, C) the 0/1 class-membership matrix,
      classMean = PᵀX_b and resLocal = (R ⊙ P)·1 (one-hot GEMMs on the
      original rows, windows on sorted ones, below) — no host-side
      index building, no per-chunk
      padding pathology for skewed classes (ADVICE r3); the population
      Gram X_bᵀX_b is built from its upper block triangle
      (``block_ls._sym_gram``: 0.5625 of the full product's MXU work
      at b = 4,096, every entry the same dot product);
    - the class-restricted products — the class sums PᵀX_b and
      Xᵀ(P ⊙ r) among the statistics, z_i = x_i·v_{y_i} and
      Σ_{i in c} x_i z_i in the CG matvec — need each row with its OWN
      class only. With ``sort`` = (order, kcls) from
      ``_class_sorted_rows`` the block's rows are gathered ONCE, after
      the statistics that read R (the Gram, XᵀR, dense in R on the
      original rows, each row's own-class residual), into class order
      as (tiles, tile, b); a tile of
      consecutive sorted rows meets a contiguous window of at most
      ``sort_window`` classes (the caller checked the class counts:
      ``_sorted_layout``), so these products are GEMMs batched over
      tiles against the window's columns (``_sorted_class_moments``,
      ``_sorted_class_products``): 2·n·b·sort_window operations a
      product where the one-hot form spends 2·n·b·C, and two reads of
      the copy an iteration. The copy costs the block's bytes again, so
      the caller takes it only where it fits (``_sorted_layout``).
      Without ``sort`` the same products ride one-hot GEMMs over all C
      classes on the original rows, (n,b)x(b,C)-shaped (3C via
      ``_limb3`` for bf16 rows): no copy, C times the operations;
    - with ``labels`` the step starts from the initial residual of
      labels whose valid rows each hold one +1 and −1 elsewhere, where
      R = (Y − jlm)·mask = 2P − (1 + jlm)·mask exactly. So XᵀR =
      2·(PᵀX_b)ᵀ − s ⊗ (1 + jlm) with s = X_bᵀmask, each row's
      own-class residual is 1 − jlm_c, Xᵀ(P ⊙ r) = (1 − jlm_c)·(PᵀX_b)_c
      and R's column means are (2·n_c − (1 + jlm_c)·Σ_k n_k) / n: the
      class sums (one windowed product on the copy), the class counts
      and s (one column reduction over X_b, before the copy, for the
      preconditioner's popMean) give them all, and R is never read;
    - all C systems share one CG loop (the per-class solves are batched
      rows of the iterate), preconditioned by the explicit inverse of
      M = (1−w)·popCov + (λ+ε)I (see ``_precond_inverse``) applied as
      one small GEMM per iteration;
    - the solve is matrix-free:
        A_c v = (1−w)·popCov·v + w·(X_cᵀ(X_c v)/n_c − μ_c(μ_cᵀv))
                + w(1−w)·δ_c(δ_cᵀv) + λv
      so no (C, b, b) covariances are ever materialized.

    Returns (Wb_new, R_new, jointMeans (C, b), exit max rel residual,
    CG iteration count). ``R`` is donated.
    """
    hp = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    C = R.shape[1]
    bf16_data = X.dtype == jnp.bfloat16

    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    Pf = P.astype(f32)

    def onehot_scale_limbs(z):
        """(n,) f32 -> (n, 3C) bf16 = the 3 limbs of P ⊙ z, built from
        z's SCALAR limbs (P is exactly 0/1 in bf16, so P·z_limb is an
        exact bf16 product) — skips materializing the (n, C) f32
        product and its 3 re-reads that ``_limb3`` would need."""
        z0 = z.astype(jnp.bfloat16)
        r1 = z - z0.astype(f32)
        z1 = r1.astype(jnp.bfloat16)
        z2 = (r1 - z1.astype(f32)).astype(jnp.bfloat16)
        return jnp.concatenate(
            [P * z0[:, None], P * z1[:, None], P * z2[:, None]], axis=1
        )

    def mm_bf16_f32_00(a_f32):
        """X_bᵀ · a for f32 ``a`` (n, k): one X_b read via limbs when
        X_b is bf16, 6-pass HIGHEST otherwise (small test problems)."""
        if bf16_data:
            return _sum3(_dot00(Xb, _limb3(a_f32, 1)), axis=1)
        return jax.lax.dot_general(
            Xb, a_f32, (((0,), (0,)), ((), ())),
            preferred_element_type=f32, precision=hp,
        )

    def mm_bf16_f32_11(a_f32):
        """X_b · aᵀ for f32 ``a`` (k, b) -> (n, k), one X_b read."""
        if bf16_data:
            return _sum3(_dot11(Xb, _limb3(a_f32, 0)), axis=1)
        return jax.lax.dot_general(
            Xb, a_f32, (((1,), (1,)), ((), ())),
            preferred_element_type=f32, precision=hp,
        )

    def mm_bf16_f32_10(a_f32):
        """X_b · a for f32 ``a`` (b, k) -> (n, k), one X_b read."""
        if bf16_data:
            return _sum3(_dot10(Xb, _limb3(a_f32, 1)), axis=1)
        return jax.lax.dot_general(
            Xb, a_f32, (((1,), (0,)), ((), ())),
            preferred_element_type=f32, precision=hp,
        )

    # -- population stats + per-class moments (pad rows of X and R are
    # zero by the Dataset padding contract) -------------------------------
    with jax.named_scope("wls.stats"):
        gram = _sym_gram(Xb)
        if labels is None:
            residual_mean = jnp.einsum("nc->c", R) / n
        if labels is not None:
            # R = 2P − (1 + jlm)·mask: its column means follow from the
            # class counts, and popMean is the kept rows' column sums
            # (every kept row has a class), one reduction over X_b
            # before the copy, so that the preconditioner needs nothing
            # of it; XᵀR and the class-restricted moments follow from
            # the copy's class sums (below)
            counts, jlm, mask = labels
            residual_mean = (
                2.0 * counts - jnp.sum(counts) * (1.0 + jlm)) / n
            pop_mean = jnp.einsum("nb->b", Xb * mask[:, None]) / n
            order, kcls = sort
        elif sort is not None:
            # XᵀR is dense in R and stays on the original rows, with the
            # labelled rows' column sums as one more column of the same
            # product (popMean, so that the preconditioner needs nothing
            # of the copy); the class-restricted moments read the sorted
            # copy (below), so of R they need each sorted row's own-class
            # entry alone, taken by index (a row of no class, or a pad
            # row, reads 0)
            labelled = jnp.max(P, axis=1).astype(f32)
            xtr = mm_bf16_f32_00(
                jnp.concatenate([R, labelled[:, None]], axis=1)) / n
            pop_xtr, pop_mean = xtr[:, :C], xtr[:, C]  # (b, C), (b,)
            order, kcls = sort
            r_sorted = _own_class_entries(R, order, kcls)
        elif bf16_data:
            # ONE X_b read for all three moment contractions: class sums
            # (one-hot columns), XᵀR (3 limbs), and Xᵀ(P⊙r) (3 limbs)
            # own-class residual per row
            r = jnp.einsum("nc,nc->n", R, Pf)
            cols = jnp.concatenate(
                [P, _limb3(R, 1), onehot_scale_limbs(r)], axis=1
            )  # (n, 7C) bf16
            G = _dot00(Xb, cols)  # (b, 7C)
            C_ = R.shape[1]
            cmean = G[:, :C_].T * inv_counts[:, None]  # (C, b)
            pop_xtr = _sum3(G[:, C_: 4 * C_], axis=1) / n  # (b, C)
            cxtr = (
                _sum3(G[:, 4 * C_:], axis=1).T * inv_counts[:, None]
            )  # (C, b)
        else:
            pop_xtr = mm_bf16_f32_00(R) / n  # (b, C)
            cmean = jax.lax.dot_general(
                Pf, Xb, (((0,), (0,)), ((), ())),
                preferred_element_type=f32, precision=hp,
            ) * inv_counts[:, None]
            r = jnp.einsum("nc,nc->n", R, Pf)
            cxtr = mm_bf16_f32_00(Pf * r[:, None]).T * inv_counts[:, None]
        if sort is None:
            rlm = jnp.einsum("nc,n->c", Pf, r) * inv_counts
            # popMean = Σ_c n_c·classMean_c / n (P already excludes pad
            # rows and empty classes contribute zero) — no extra X pass
            counts = valid / inv_counts
            pop_mean = jnp.einsum("c,cb->b", counts, cmean) / n
        pop_cov = gram / n - jnp.outer(pop_mean, pop_mean)

    with jax.named_scope("wls.precond"):
        Minv = _precond_inverse(pop_cov, w, lam)

    def onehot_xxv(v):  # (C, b) -> Σ_{i in c} x_i (x_i·v_c), (C, b)
        T = mm_bf16_f32_11(v)  # (n, C) rows X_b·v_c for every class c
        z = jnp.einsum("nc,nc->n", T, Pf)  # pick own-class entry
        if bf16_data:
            return _sum3(_dot00(Xb, onehot_scale_limbs(z)), axis=1).T
        return mm_bf16_f32_00(Pf * z[:, None]).T

    if sort is None:
        class_xxv = onehot_xxv
    else:
        with jax.named_scope("wls.sort"):
            # the copy is the block's bytes again: the barrier keeps its
            # gather behind every read of R, so that R (n, C) is dead by
            # then in a fit of one block step, and behind the
            # preconditioner, whose factorisation's temporaries are dead
            # by then too (the gather depends on nothing else, and XLA
            # is free to schedule it first)
            if labels is None:
                order, r_sorted, pop_xtr, pop_mean, Minv, residual_mean = (
                    jax.lax.optimization_barrier(
                        (order, r_sorted, pop_xtr, pop_mean, Minv,
                         residual_mean))
                )
            else:
                order, pop_mean, Minv, residual_mean = (
                    jax.lax.optimization_barrier(
                        (order, pop_mean, Minv, residual_mean))
                )
            Xs = Xb.at[order].get(mode="promise_in_bounds")
        with jax.named_scope("wls.stats"):
            if labels is None:
                csum, crsum, rsum = _sorted_class_moments(
                    Xs, kcls, r_sorted, C, sort_window
                )
                cmean = csum * inv_counts[:, None]
                cxtr = crsum * inv_counts[:, None]
                rlm = rsum * inv_counts
            else:
                csum = _sorted_class_moments(Xs, kcls, None, C, sort_window)
                cmean = csum * inv_counts[:, None]
                # every row of class c holds the residual 1 − jlm_c in c
                rlm = (1.0 - jlm) * valid
                cxtr = cmean * rlm[:, None]
                pop_xtr = (
                    2.0 * csum.T / n - pop_mean[:, None] * (1.0 + jlm)
                )  # (b, C)
        class_xxv = _sorted_class_products(Xs, kcls, C, sort_window)

    with jax.named_scope("wls.stats"):
        mean_diff = cmean - pop_mean[None, :]
        jm = cmean * w + pop_mean[None, :] * (1.0 - w)
        mmw = residual_mean * (1.0 - w) + w * rlm
        joint_xtr = pop_xtr.T * (1.0 - w) + cxtr * w - jm * mmw[:, None]
        rhs = joint_xtr - Wb.T * lam  # (C, b)

    def matvec(v):  # (C, b) -> (C, b)
        pv = (1.0 - w) * jnp.matmul(v, pop_cov, precision=hp)
        xxv = class_xxv(v)
        cm_dot = jnp.einsum("gb,gb->g", cmean, v, precision=hp)
        ccov_v = xxv * inv_counts[:, None] - cmean * cm_dot[:, None]
        dd = (
            mean_diff
            * jnp.einsum("gb,gb->g", mean_diff, v, precision=hp)[:, None]
            * (w * (1.0 - w))
        )
        return pv + w * ccov_v + dd + lam * v

    def minv(r_):  # explicit-inverse preconditioner as ONE GEMM
        return jnp.matmul(r_, Minv, precision=hp)

    tiny = jnp.asarray(1e-30, f32)
    b_norm = jnp.maximum(jnp.linalg.norm(rhs, axis=1), tiny)

    def rel_res(r_):
        return jnp.max(jnp.linalg.norm(r_, axis=1) / b_norm)

    def cg_loop(mv, x_init, r_init, it_init, iter_cap, exit_tol):
        def cond(state):
            it, x, r_, z, p_, rz = state
            return jnp.logical_and(it < iter_cap, rel_res(r_) > exit_tol)

        def body(state):
            it, x, r_, z, p_, rz = state
            Ap = mv(p_)
            denom = jnp.einsum("gb,gb->g", p_, Ap, precision=hp)
            alpha = jnp.where(
                denom > 0, rz / jnp.maximum(denom, tiny), 0.0
            )
            x = x + alpha[:, None] * p_
            r_ = r_ - alpha[:, None] * Ap
            z = minv(r_)
            rz_new = jnp.einsum("gb,gb->g", r_, z, precision=hp)
            beta = jnp.where(rz > 0, rz_new / jnp.maximum(rz, tiny), 0.0)
            p_ = z + beta[:, None] * p_
            return it + 1, x, r_, z, p_, rz_new

        z0 = minv(r_init)
        rz0 = jnp.einsum("gb,gb->g", r_init, z0, precision=hp)
        return jax.lax.while_loop(
            cond, body, (it_init, x_init, r_init, z0, z0, rz0)
        )

    # single-phase exact-operator CG. (A two-phase variant — 2-limb
    # warm start + exact restart — was measured at parity: the cheaper
    # operator's error perturbs the CG directions enough that total
    # iterations grow ~20%, cancelling the per-iteration savings.)
    with jax.named_scope("wls.cg"):
        x0 = jnp.zeros_like(rhs)
        it, dW, r_fin, _, _, _ = cg_loop(
            matvec, x0, rhs, jnp.asarray(0), max_iters, tol
        )
        rel = rel_res(r_fin)

    # -- apply the update --------------------------------------------------
    with jax.named_scope("wls.update"):
        delta = (dW * valid[:, None]).T  # (b, C), empty classes masked
        Wb_new = Wb + delta
        R_new = R - mm_bf16_f32_10(delta)
    return Wb_new, R_new, jm * valid[:, None], rel, it


@partial(
    jax.jit,
    static_argnames=("width", "n", "max_iters", "tol", "sort_window"),
    donate_argnums=(1,),
)
def _pcg_block_step(X, R, P, Wb, inv_counts, valid, start, w, lam,
                    sort=None, labels=None, *, width, n, max_iters=96,
                    tol=1e-6, sort_window=0):
    """Single-block dispatch of ``_pcg_block_core`` (used for non-uniform
    tail blocks and host-RAM slabs; uniform-width fits go through
    ``_pcg_fit_full``)."""
    return _pcg_block_core(X, R, P, Wb, inv_counts, valid, start, w, lam,
                           sort, labels, width=width, n=n,
                           max_iters=max_iters, tol=tol,
                           sort_window=sort_window)


def _membership(Y, mask):
    """One-hot class membership P (n, C) (bf16, exact 0/1; pad rows
    zero) and the rows of each class (C,)."""
    # Class membership must match the chol path / the reference
    # (indexOf(label.max), i.e. argmax with first-index tie-breaking,
    # BlockWeightedLeastSquares.scala) — an explicit argmax + one_hot
    # measured 58 ms at the flagship shape, so membership is the FIRST
    # positive entry per row instead: pos ∧ (cumsum(pos) == 1) is a
    # fused ~1 ms pass, and for indicator labels (ClassLabelIndicators:
    # entries in {−1, +1}, possibly multi-hot) every positive entry
    # ties at +1, so first-positive IS argmax. Rows with no positive
    # entry (pad rows, malformed labels) belong to no class. Contract:
    # labels whose positive entries are NOT all equal (arbitrary
    # real-valued Y) would need a true argmax — the estimator's
    # docstring pins indicator-style labels for this path.
    pos = Y > 0
    first_pos = pos & (jnp.cumsum(pos, axis=1) == 1)
    P = first_pos.astype(jnp.bfloat16) * mask[:, None].astype(jnp.bfloat16)
    return P, jnp.einsum("nc->c", P.astype(jnp.float32))


@jax.jit
def _class_counts(Y, mask):
    """Rows of each class (C,), and whether every row that ``mask``
    keeps (1; 0 drops it) is a single-label ±1 indicator — one entry +1,
    every other −1 — for the host's choices before the program is
    enqueued (``_sorted_layout``): the one read-back a sorted fit
    makes. Such labels give the fit's first block step its moments from
    the class sums (``_pcg_block_core``'s ``labels``)."""
    # the positives a row holds are the last column of the membership's
    # running count (the same op, computed once)
    positives = jnp.cumsum(Y > 0, axis=1)[:, -1]
    row_single = (positives == 1) & jnp.all(jnp.abs(Y) == 1.0, axis=1)
    single = jnp.all(jnp.where(mask == 1.0, row_single, mask == 0.0))
    return _membership(Y, mask)[1], single


def _pcg_setup_core(Y, mask, w, n, sort_tile=0):
    with jax.named_scope("wls.setup"):
        P, counts = _membership(Y, mask)
        inv_counts = 1.0 / jnp.maximum(counts, 1.0)
        valid = (counts > 0).astype(jnp.float32)
        # jointLabelMean[c] = 2w + 2(1-w)·n_c/n − 1 (reference :148-155)
        jlm = 2.0 * w + 2.0 * (1.0 - w) * counts / n - 1.0
        R = (Y - jlm[None, :]) * mask[:, None]
        sort = _class_sorted_rows(P, sort_tile) if sort_tile else None
    return P, counts, inv_counts, valid, jlm, R, sort


@partial(jax.jit, static_argnames=("n", "sort_tile"))
def _pcg_setup(Y, mask, w, *, n, sort_tile=0):
    """One-hot class membership P (bf16, exact 0/1), per-class counts
    (and their inverses and validity), joint label mean, the initial
    residual and, with ``sort_tile``, the rows' order by class — all on
    device (the r3 implementation synced
    class ids to host and built gather indices in a Python loop over
    classes, ~250 ms of the flagship fit). Dispatch wrapper for the
    ragged-block path; uniform fits use the fully fused
    ``_pcg_fit_full``."""
    return _pcg_setup_core(Y, mask, w, n, sort_tile)


@partial(
    jax.jit,
    static_argnames=("width", "n", "num_iter", "max_iters", "tol",
                     "sort_tile", "sort_window", "label_moments"),
)
def _pcg_fit_full(X, Y, mask, starts, w, lam,
                  *, width, n, num_iter, max_iters=96, tol=1e-5,
                  sort_tile=0, sort_window=0, label_moments=False):
    """The ENTIRE weighted-BCD fit — label setup (with ``sort_tile``,
    the rows' order by class too), every epoch's scanned block updates,
    model concatenation, and the intercept — as ONE jitted program: a
    single dispatch and zero host work per fit. With ``label_moments``
    (sorted rows and single-label ±1 indicator labels: ``_class_counts``)
    the first block step runs before the scan and takes its moments
    from the class sums (``_pcg_block_core``'s ``labels``).
    Returns (W (D, C), intercept (C,), max rel residual, max CG iters).
    """
    P, counts, inv_counts, valid, jlm, R, sort = _pcg_setup_core(
        Y, mask, w, n, sort_tile
    )
    C = Y.shape[1]
    nb = starts.shape[0]
    W0 = jnp.zeros((nb, width, C), jnp.float32)

    def step(carry, xs, labels=None):
        R_c, Wstack = carry
        i, start = xs
        Wb_new, R_new, jm, rel, its = _pcg_block_core(
            X, R_c, P, Wstack[i], inv_counts, valid, start, w, lam,
            sort, labels, width=width, n=n, max_iters=max_iters, tol=tol,
            sort_window=sort_window,
        )
        Wstack = jax.lax.dynamic_update_index_in_dim(
            Wstack, Wb_new, i, axis=0
        )
        return (R_new, Wstack), (jm, rel, its)

    idx = jnp.tile(jnp.arange(nb), num_iter)
    all_starts = jnp.tile(starts, num_iter)
    carry, outs = (R, W0), []
    if label_moments:
        # in a fit of one block step R_new is dead, and so is R
        carry, out = step(carry, (idx[0], all_starts[0]),
                          (counts, jlm, mask))
        outs.append(jax.tree.map(lambda a: a[None], out))
    first = len(outs)
    if idx.shape[0] > first:
        carry, out = jax.lax.scan(
            step, carry, (idx[first:], all_starts[first:])
        )
        outs.append(out)
    Wstack = carry[1]
    jms, rels, itss = (jnp.concatenate(parts) for parts in zip(*outs))
    # blocks are contiguous ascending column ranges: stacking IS the
    # feature-axis concatenation
    W = Wstack.reshape(nb * width, C)
    jm_full = jnp.transpose(jms[-nb:], (1, 0, 2)).reshape(C, nb * width)
    # finalB = jointLabelMean − Σ_d jointMeans[c,d]·W[d,c] (:311-314)
    intercept = jlm - jnp.einsum("cd,dc->c", jm_full, W)
    return W, intercept, jnp.max(rels), jnp.max(itss)


@partial(jax.jit, static_argnames=("m", "width"))
def _class_chunk_stats_gathered(
    X, R, idx_c, wt_c, counts_c, class_ids, start, *, m, width,
):
    """Gathered-layout variant of ``_class_chunk_stats`` (same returns);
    pads only to the chunk's own max class size."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    Xc = Xb[idx_c] * wt_c[:, :, None].astype(Xb.dtype)
    inv = 1.0 / counts_c
    r_g = R[idx_c, class_ids[:, None]] * wt_c
    class_mean, class_xtr, res_local_mean = _chunk_moments(Xc, r_g, inv)
    hp = (
        jax.lax.Precision.HIGHEST
        if Xc.dtype == jnp.float32 else None
    )
    class_cov = (
        jnp.einsum("gmb,gmc->gbc", Xc, Xc,
                   preferred_element_type=jnp.float32, precision=hp)
        * inv[:, None, None]
        - class_mean[:, :, None] * class_mean[:, None, :]
    )
    return class_cov, class_mean, class_xtr, res_local_mean


def _count_fit(solve: str, layout: str, widths, num_iter: int,
               label_moments: bool = False) -> None:
    """Count one weighted fit started, the path it takes and the column
    pairs of the Grams it builds: one a block of ``widths`` a sweep."""
    _count_gram_pairs(widths, times=num_iter)
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_wls_fits_total",
        "mixture-weighted block least-squares fits started",
    ).inc()
    reg.counter(
        "keystone_solver_wls_path_total",
        "weighted fits by the solver and row layout taken",
        labelnames=("solve", "layout"),
    ).inc((solve, layout))
    reg.counter(
        "keystone_solver_wls_sorted_fits_total",
        "weighted fits whose CG matvec ran on class-sorted rows",
    ).inc(by=int(layout == "sorted"))
    reg.counter(
        "keystone_solver_wls_sorted_stats_fits_total",
        "weighted fits whose statistics read class-sorted rows",
    ).inc(by=int(layout == "sorted"))
    reg.counter(
        "keystone_solver_wls_label_moments_fits_total",
        "weighted fits whose first block step took its moments from "
        "the class sums",
    ).inc(by=int(label_moments))


# The sorted-rows matvec's shapes. A tile of _SORT_TILE class-sorted rows
# (a fit of fewer rows is one tile) is multiplied by a window of
# _SORT_WINDOW class vectors: one MXU width — a narrower window would
# leave the array's columns idle, a wider one spends operations on
# classes the tile does not hold. ImageNet's, TIMIT's and VOC's classes
# all keep a tile inside a window; smaller tiles (4,096, 1,024 rows)
# measured slower on the flagship's rows and serve no workload yet.
_SORT_WINDOW = 128
_SORT_TILE = 16384


def _tiles_fit_window(counts, tile: int) -> bool:
    """Whether every tile of ``tile`` consecutive class-sorted rows
    spans at most _SORT_WINDOW classes, empty ones between them
    included (False too when no row has a class)."""
    ends = np.cumsum(np.asarray(counts, np.int64))
    labelled = int(ends[-1])
    if labelled == 0:
        return False
    first = np.arange(0, labelled, tile)
    last = np.minimum(first + tile, labelled) - 1
    # sorted row j lies in the first class whose rows end beyond j
    span = (np.searchsorted(ends, last, "right")
            - np.searchsorted(ends, first, "right") + 1)
    return bool(span.max() <= _SORT_WINDOW)


def _sorted_layout(X, Y, mask, width: int,
                   block_steps: int) -> tuple[int, bool]:
    """Whether this fit's CG matvec reads class-sorted rows, as the
    tile to sort into (0: the one-hot matvec on the original rows).
    Decided from what can be seen before the program is enqueued:

    - the rows live on one device (a global sort of sharded rows would
      move every row between devices each block step);
    - the copy fits: what the program holds at its peak stays under
      nine tenths of ``_device_memory_limit()`` (the tenth is for the
      (b, b) and (C, b) arrays and whatever else the process holds).
      That is X, the labels, the block's rows again and two
      (tiles, tile, window) products; in a fit of several block steps
      also the residual (carried, and written anew by the update), the
      membership and, where the block is narrower than X, its columns
      cut out of X before they are sorted. A fit of one block step
      holds none of these: XLA frees the statistics' arrays before the
      copy is made. Checked against the TPU compiler's own count at
      the flagship's shape (n 327,680, D 4,096, C 1,000; compiler |
      here): one step 12.51 | 12.38 GB, two blocks of 2,048 14.98 |
      15.66, one block twice 15.30 | 15.66 (the class-restricted
      statistics read the copy and hold no (n, C) temporary);
    - the class counts keep every tile inside one window — the one
      read-back, made last.

    Returns (tile, whether the first block step takes its moments from
    the class sums): the second from the same read-back
    (``_class_counts``), and only with a tile.
    """
    sharding = getattr(X, "sharding", None)  # a host array has none
    if sharding is not None and len(sharding.device_set) > 1:
        return 0, False
    tile = min(_SORT_TILE, -(-X.shape[0] // 8) * 8)
    rows = -(-X.shape[0] // tile) * tile
    block = rows * width * X.dtype.itemsize
    need = X.nbytes + Y.nbytes + block + 2 * rows * _SORT_WINDOW * 4
    if block_steps > 1:
        need += 2.5 * Y.nbytes + (block if width < X.shape[1] else 0)
    if need > 0.9 * _device_memory_limit():
        return 0, False
    counts, single = jax.device_get(_class_counts(Y, mask))
    if not _tiles_fit_window(counts, tile):
        return 0, False
    return tile, bool(single)


@dataclasses.dataclass(eq=False)
class BlockWeightedLeastSquaresEstimator(LabelEstimator):
    """fit(features, ±1 indicator labels) -> BlockLinearMapper
    (reference: BlockWeightedLeastSquares.scala:36; weight=(3·numIter)+1).

    Label contract: indicator-style matrices (ClassLabelIndicators —
    entries in {−1, +1}). Each row's class is its argmax with
    first-index tie-breaking, matching the reference's
    indexOf(label.max): multi-hot rows join exactly ONE class (the
    first positive) in BOTH solver paths. Arbitrary real-valued Y with
    unequal positive entries is outside the contract — the pcg path
    keys on the first positive entry, not the largest. Single-label
    indicators (every valid row one +1, −1 elsewhere, as
    ClassLabelIndicators makes them) on the pcg path's sorted rows give
    the first block step its XᵀR and own-residual moments from the class
    sums, the same model up to float32 summation order; other labels,
    and every later block step, take the products with R."""

    block_size: int
    num_iter: int
    lam: float
    mixture_weight: float
    num_features: Optional[int] = None
    class_chunk: int = 16  # classes per batched device step (chol path)
    solve: str = "auto"  # "chol": exact batched per-class Cholesky over
    # the class-grouped layout | "pcg": batched matrix-free
    # preconditioned CG (never materializes class covariances, the
    # padded grouped copy, or the C per-class b³/3 factorizations —
    # each class has a single rhs); its matvec reads class-sorted rows
    # where one more copy of a block fits the device and the class
    # counts allow, the original rows otherwise (``_sorted_layout``
    # decides a fit; ``keystone_solver_wls_path_total`` says which) |
    # "auto": pcg when the first block is wide (≥1024, where
    # factorizations dominate) and w ≤ 0.9 (as w→1 the shared popCov
    # preconditioner drains and CG may hit its iteration cap), chol
    # otherwise
    layout: str = "auto"  # chol-path row layout: "grouped" (one padded
    # (C, m, ·) gather), "gathered" (per-chunk gathers, for skewed
    # classes / tight HBM), "auto" (grouped iff padding ≤ ~1.5n AND the
    # copy fits a third of device memory — ADVICE r3). The pcg path
    # takes no layout from here: it chooses its own (see ``solve``)
    convergence_check: str = "warn"  # after a pcg/auto fit, read the
    # max CG exit residual and "warn" / "raise" when it exceeds
    # ``pcg_tol`` (a capped CG exit would otherwise pass silently —
    # ADVICE r3). The read syncs the dispatch stream;
    # latency-critical callers set "off" and check
    # ``model.solver_info['pcg_max_rel_residual']`` themselves.
    pcg_tol: float = 1e-5  # CG exit: relative residual per class. At
    # 1e-5 the solution error vs the exact per-class solve is ~κ·tol ≈
    # 1e-4 relative (the fixture suite asserts pcg↔chol agreement at
    # 5e-4 and vs an f64 reference at 2e-2) — far below feature noise;
    # tighten to 1e-6 when comparing solvers numerically (≈3 extra CG
    # iterations per block).

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        if self.solve not in ("auto", "chol", "pcg"):
            raise ValueError(
                f"solve must be 'auto', 'chol', or 'pcg', got {self.solve!r}"
            )
        if self.convergence_check not in ("off", "warn", "raise"):
            raise ValueError(
                "convergence_check must be 'off', 'warn', or 'raise', "
                f"got {self.convergence_check!r}"
            )
        if self.layout not in ("auto", "grouped", "gathered"):
            raise ValueError(
                "layout must be 'auto', 'grouped', or 'gathered', "
                f"got {self.layout!r}"
            )
        if data.is_host:
            # out-of-aggregate-HBM fit: host-RAM column blocks streamed
            # per pass (the BlockLS host mode, block_ls.py). Only the
            # matrix-free PCG solver applies — it is the auto choice at
            # the wide blocks where host-blocking matters, and the chol
            # path's class-grouped row layouts are built from a
            # device-resident X.
            if self.solve == "chol":
                raise ValueError(
                    "host-blocks datasets require the pcg solver "
                    "(solve='auto' or 'pcg'); the chol path gathers "
                    "class-grouped layouts from a device-resident X"
                )
            _count_fit("pcg", "host_blocks", data.block_widths,
                       self.num_iter)
            with span("solver.wls.dispatch"):
                model = self._fit_pcg_host(data, labels)
            self._check_convergence(model.solver_info)
            return model
        with span("solver.wls.prep"):
            data = data.to_array_mode()
            labels = labels.to_array_mode()
            X = data.padded()
            Y = labels.padded().astype(jnp.float32)
        n = data.n
        D = X.shape[1]
        blocks = [
            (s, min(s + self.block_size, D) - s)
            for s in range(0, D, self.block_size)
        ]
        # one solver per fit (blocks share the residual's physical
        # layout): PCG for wide blocks — there the C per-class b³/3
        # factorizations dominate — but not as w→1, where the shared
        # popCov preconditioner drains and CG may hit its iteration cap
        use_pcg = self.solve == "pcg" or (
            self.solve == "auto"
            and blocks[0][1] >= 1024
            and self.mixture_weight <= 0.9
        )
        with span("solver.wls.dispatch"):
            if use_pcg:
                model = self._fit_pcg(data, X, Y, n, blocks)
            else:
                model = self._fit_chol(data, X, Y, n, blocks)
        self._check_convergence(model.solver_info)
        return model

    def _fit_pcg(self, data, X, Y, n, blocks):
        """Batched all-class PCG (see ``_pcg_block_core``): one
        dispatch per fit or per block, and no host work but the choice
        of the matvec's row layout and of the first block step's
        moments (``_sorted_layout``)."""
        w = self.mixture_weight
        mask = data.mask()
        C = Y.shape[1]
        with span("solver.wls.layout"):
            tile, single = _sorted_layout(
                X, Y, mask, max(wd for _, wd in blocks),
                len(blocks) * self.num_iter)
        _count_fit("pcg", "sorted" if tile else "original",
                   [wd for _, wd in blocks], self.num_iter,
                   label_moments=single)
        window = _SORT_WINDOW if tile else 0
        if len({wd for _, wd in blocks}) == 1:
            # uniform widths (every real config: block_size divides D or
            # one block): the ENTIRE fit — setup, every epoch's scanned
            # block updates, concatenation, intercept — is one jitted
            # program and one dispatch (_pcg_fit_full)
            wd = blocks[0][1]
            starts = jnp.asarray([s for s, _ in blocks], jnp.int32)
            W, intercept, pcg_rel, pcg_iters = _pcg_fit_full(
                X, Y, mask, starts, w, self.lam, width=wd, n=n,
                num_iter=self.num_iter, tol=self.pcg_tol, sort_tile=tile,
                sort_window=window, label_moments=single,
            )
            return BlockLinearMapper(
                W, self.block_size, explicit_intercept=intercept,
                solver_info={"pcg_max_rel_residual": pcg_rel,
                             "pcg_iterations": pcg_iters},
            )
        # ragged tail block: one dispatch per block
        P, counts, inv_counts, valid, jlm, R, sort = _pcg_setup(
            Y, mask, w, n=n, sort_tile=tile
        )
        # the first block step alone starts from the labels' residual
        labels = (counts, jlm, mask) if single else None
        Wb = {s: jnp.zeros((wd, C), jnp.float32) for s, wd in blocks}
        joint_means = {}
        pcg_rel = None  # max CG exit residual across block solves
        pcg_iters = None  # max CG iteration count (at the cap together
        # with a large residual = preconditioner ill-suited for this
        # mixture weight; see solve= docstring)
        for _ in range(self.num_iter):
            for s, wd in blocks:
                Wb[s], R, jm, rel, its = _pcg_block_step(
                    X, R, P, Wb[s], inv_counts, valid, s,
                    w, self.lam, sort, labels, width=wd, n=n,
                    tol=self.pcg_tol, sort_window=window,
                )
                labels = None
                joint_means[s] = jm
                pcg_rel = rel if pcg_rel is None else (
                    jnp.maximum(pcg_rel, rel)
                )
                pcg_iters = its if pcg_iters is None else (
                    jnp.maximum(pcg_iters, its)
                )

        return self._finish(blocks, Wb, joint_means, jlm, {
            "pcg_max_rel_residual": pcg_rel,
            "pcg_iterations": pcg_iters,
        })

    def _fit_pcg_host(self, data, labels) -> BlockLinearMapper:
        """Weighted BCD from HOST-RAM feature blocks: each slab rides an
        async ``device_put`` double-buffered against the previous
        block's whole-block PCG program (same streaming discipline as
        ``BlockLeastSquaresEstimator._fit_host_blocks``; the slab stays
        resident for all of its block's CG iterations, so transfer
        volume is one slab per block per sweep). The dataset's own
        block layout IS the coordinate blocking, matching the
        reference's Seq-of-per-block-RDDs."""
        from keystone_tpu.ops.learning.block_ls import _RunAheadLimiter

        lab = labels.to_array_mode()
        if lab.padded_n != data.padded_n:
            lab = lab._pad_to(data.padded_n)
        Y = lab.padded().astype(jnp.float32)
        n = data.n
        mask = data.mask()
        w = self.mixture_weight
        host_blocks = data.host_blocks
        widths = data.block_widths
        starts = np.cumsum([0] + widths[:-1]).tolist()
        blocks = list(zip(starts, widths))
        C = Y.shape[1]

        P, _, inv_counts, valid, jlm, R, _ = _pcg_setup(Y, mask, w, n=n)
        Wb = {s: jnp.zeros((wd, C), jnp.float32) for s, wd in blocks}
        joint_means = {}
        pcg_rel = None
        pcg_iters = None
        limiter = _RunAheadLimiter()
        schedule = [
            (it, bi)
            for it in range(self.num_iter)
            for bi in range(len(blocks))
        ]
        nxt = jax.device_put(host_blocks[schedule[0][1]])
        for j, (it, bi) in enumerate(schedule):
            Xb = nxt
            if j + 1 < len(schedule):
                nxt = jax.device_put(host_blocks[schedule[j + 1][1]])
            s, wd = blocks[bi]
            # the slab IS the block: start=0, width=slab width
            Wb[s], R, jm, rel, its = _pcg_block_step(
                Xb, R, P, Wb[s], inv_counts, valid, 0, w, self.lam,
                width=wd, n=n, tol=self.pcg_tol,
            )
            joint_means[s] = jm
            pcg_rel = rel if pcg_rel is None else jnp.maximum(pcg_rel, rel)
            pcg_iters = (
                its if pcg_iters is None else jnp.maximum(pcg_iters, its)
            )
            del Xb
            limiter.add(Wb[s])

        return self._finish(blocks, Wb, joint_means, jlm, {
            "pcg_max_rel_residual": pcg_rel,
            "pcg_iterations": pcg_iters,
        })

    def _check_convergence(self, solver_info: Optional[dict]) -> None:
        if self.convergence_check == "off" or solver_info is None:
            return
        # reading the device scalars syncs the dispatch stream; the CG
        # loop exits with rel <= tol unless the iteration cap hit
        with span("solver.wls.converged"):
            rel_val = float(solver_info["pcg_max_rel_residual"])
            iters = int(solver_info["pcg_iterations"])
        get_global_registry().counter(
            "keystone_solver_wls_pcg_iterations_total",
            "CG iterations of weighted fits (the most of any block "
            "step), counted where convergence_check reads them",
        ).inc(by=iters)
        if rel_val > self.pcg_tol:
            msg = (
                f"weighted PCG hit its iteration cap "
                f"(max {iters} iters) with max relative "
                f"residual {rel_val:.2e} > tol {self.pcg_tol:.0e}; "
                "the fit may be under-converged — try solve='chol', "
                "a smaller mixture_weight, or a larger lam"
            )
            if self.convergence_check == "raise":
                raise RuntimeError(msg)
            import warnings

            warnings.warn(msg, stacklevel=2)

    def _fit_chol(self, data, X, Y, n, blocks):
        """Exact batched per-class Cholesky path (narrow blocks / w→1).
        Needs per-class covariances, so rows are class-grouped — ONE
        device gather into a padded (C, m, ·) layout when that fits the
        memory budget, per-chunk gathers padded to the chunk's own max
        otherwise (skewed classes or tight HBM; ADVICE r3). The weighted
        solve is row-permutation invariant, so the layout choice changes
        nothing numerically."""
        w = self.mixture_weight
        D = X.shape[1]
        C = Y.shape[1]
        class_of = np.asarray(jnp.argmax(Y, axis=1))[: n]
        counts = np.bincount(class_of, minlength=C).astype(np.int64)
        # Classes with no examples get no model update (the reference's
        # groupByClasses simply yields no partition for them; the suite's
        # "empty partitions" / "1 class only" tests exercise this).
        valid_class = counts > 0
        m = int(counts.max())
        grouped_bytes = (C * m) * (
            D * X.dtype.itemsize + C * 4  # Xg copy + R in grouped order
        )
        if self.layout == "auto":
            # grouped only when the padding stays modest AND the copy
            # fits the memory budget (a dataset already filling HBM must
            # not be doubled — ADVICE r3)
            use_grouped = (
                C * m <= int(1.5 * n) + 4096
                and grouped_bytes <= 0.33 * _device_memory_limit()
            )
        else:
            use_grouped = self.layout == "grouped"
        _count_fit("chol", "grouped" if use_grouped else "gathered",
                   [wd for _, wd in blocks], self.num_iter)
        # clamp to 1 so empty-class divisions stay finite; their zero wt
        # rows already zero the numerators, and their delta is masked out
        counts_j = jnp.asarray(np.maximum(counts, 1), jnp.float32)
        valid_j = jnp.asarray(valid_class, jnp.float32)

        # jointLabelMean[c] = 2w + 2(1-w)·n_c/n − 1 (reference :148-155)
        joint_label_mean = jnp.asarray(
            2 * w + 2 * (1 - w) * counts / n - 1.0, jnp.float32
        )

        rows_of = {
            c: np.flatnonzero(class_of == c).astype(np.int32)
            for c in range(C)
        }
        if use_grouped:
            idx = np.zeros((C, m), np.int32)
            wt = np.zeros((C, m), np.float32)
            for c in range(C):
                idx[c, : counts[c]] = rows_of[c]
                wt[c, : counts[c]] = 1.0
            idx = jnp.asarray(idx)
            wt = jnp.asarray(wt)
            XX, R = _group_rows(X, Y, idx, wt, joint_label_mean)
            mask = wt.reshape(-1)
            chunk_order = list(range(C))
        else:
            XX = X
            mask = data.mask()
            R = (Y - joint_label_mean[None, :]) * mask[:, None]
            # chunk classes in DESCENDING size order so same-size classes
            # share a chunk and per-chunk padding stays small
            chunk_order = list(np.argsort(-counts, kind="stable"))

        Wb = {s: jnp.zeros((wd, C), jnp.float32) for s, wd in blocks}
        joint_means = {}  # per block: (C, b)
        chunks = [
            chunk_order[g : g + self.class_chunk]
            for g in range(0, C, self.class_chunk)
        ]
        if not use_grouped:
            # per-chunk gather indices, padded to the chunk's own max
            # (pow2-rounded so compile count stays bounded)
            chunk_idx = {}
            for ci, chunk in enumerate(chunks):
                mc = max(1, max(int(counts[c]) for c in chunk))
                mc = 1 << (mc - 1).bit_length()
                ic = np.zeros((len(chunk), mc), np.int32)
                wc = np.zeros((len(chunk), mc), np.float32)
                for g, c in enumerate(chunk):
                    ic[g, : counts[c]] = rows_of[c]
                    wc[g, : counts[c]] = 1.0
                chunk_idx[ci] = (jnp.asarray(ic), jnp.asarray(wc), mc)

        for _ in range(self.num_iter):
            for s, wd in blocks:
                pop_mean, pop_cov, pop_xtr = _pop_stats(
                    XX, R, mask, s, width=wd, n=n
                )
                residual_mean = (
                    jnp.einsum("nc->c", R) / n
                )  # MatrixUtils.computeMean over all rows
                delta = jnp.zeros((wd, C), jnp.float32)
                jm_block = jnp.zeros((C, wd), jnp.float32)
                for ci, chunk in enumerate(chunks):
                    cids = jnp.asarray(np.asarray(chunk, np.int32))
                    if use_grouped:
                        ccov, cmean, cxtr, rlm = _class_chunk_stats(
                            XX, R, wt, counts_j, cids, int(chunk[0]),
                            s, G=len(chunk), m=m, width=wd,
                        )
                    else:
                        ic, wc, mc = chunk_idx[ci]
                        ccov, cmean, cxtr, rlm = (
                            _class_chunk_stats_gathered(
                                XX, R, ic, wc, counts_j[cids], cids,
                                s, m=mc, width=wd,
                            )
                        )
                    mean_diff = cmean - pop_mean[None, :]
                    joint_xtx = (
                        pop_cov[None] * (1.0 - w)
                        + ccov * w
                        + mean_diff[:, :, None]
                        * mean_diff[:, None, :]
                        * ((1.0 - w) * w)
                    )
                    jm = cmean * w + pop_mean[None, :] * (1.0 - w)
                    mmw = residual_mean[cids] * (1.0 - w) + w * rlm
                    joint_xtr = (
                        pop_xtr[:, cids].T * (1.0 - w)
                        + cxtr * w
                        - jm * mmw[:, None]
                    )
                    rhs = joint_xtr - Wb[s][:, cids].T * self.lam
                    dW = _batched_psd_solve(joint_xtx, rhs, self.lam)
                    v = valid_j[cids][:, None]
                    delta = delta.at[:, cids].set((dW * v).T)
                    jm_block = jm_block.at[cids].set(jm * v)
                Wb[s] = Wb[s] + delta
                joint_means[s] = jm_block
                R = _apply_delta(XX, R, delta, s, width=wd)

        return self._finish(
            blocks, Wb, joint_means, joint_label_mean, None
        )

    def _finish(self, blocks, Wb, joint_means, joint_label_mean,
                solver_info):
        W = jnp.concatenate([Wb[s] for s, _ in blocks], axis=0)
        jm_full = jnp.concatenate(
            [joint_means[s] for s, _ in blocks], axis=1
        )  # (C, D)
        # finalB = jointLabelMean − Σ_d jointMeans[c,d]·W[d,c] (:311-314)
        intercept = joint_label_mean - jnp.einsum("cd,dc->c", jm_full, W)
        return BlockLinearMapper(
            W, self.block_size, explicit_intercept=intercept,
            # lazy device scalars: reading them syncs, ignoring is free —
            # surfaces a PCG iteration-cap exit instead of failing silently
            solver_info=solver_info,
        )

    @property
    def weight(self) -> int:
        return (3 * self.num_iter) + 1


@partial(jax.jit, static_argnames=("width", "first_pass"))
def _rwls_block_step(X, mu_b, B, y_zm, res, Wb, aTa, lam_eye, start,
                     *, width, first_pass):
    """One ReWeightedLeastSquaresSolver block update (reference:
    internal/ReWeightedLeastSquares.scala:80-137):
        aTa   = X̃ᵀ(B ∘ X̃)               (pass 0, cached)
        res'  = res − B ∘ (X̃ W_old)
        aTb   = X̃ᵀ(B ∘ y − res')
        W_new = (aTa + λI) \\ aTb
        res   = res' + B ∘ (X̃ W_new)
    """
    Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=1)
    Xzm = (Xb - mu_b[None, :]) * (B > 0)[:, None]  # B>0 masks pad rows
    BX = Xzm * B[:, None]
    if first_pass:
        aTa = _f32_mm(Xzm.T, BX)
    res_upd = res - _f32_mm(BX, Wb)
    aTb = _f32_mm(Xzm.T, (y_zm * B)[:, None] - res_upd)
    Wb_new = jax.scipy.linalg.solve(aTa + lam_eye, aTb, assume_a="pos")
    res_new = res_upd + _f32_mm(BX, Wb_new)
    return Wb_new, res_new, aTa


@dataclasses.dataclass(eq=False)
class PerClassWeightedLeastSquaresEstimator(LabelEstimator):
    """Same mixture-weighted objective solved class-by-class via reweighted
    single-output BCD (reference: PerClassWeightedLeastSquares.scala:31,
    63-227 + internal/ReWeightedLeastSquares.scala:18,36). Weight vector
    per class c: (1−w)/n everywhere plus w/n_c on class-c rows; features
    centered by the per-class joint mean, labels by the joint label mean."""

    block_size: int
    num_iter: int
    lam: float
    mixture_weight: float
    num_features: Optional[int] = None

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        data = data.to_array_mode()
        labels = labels.to_array_mode()
        X = data.padded()
        Y = labels.padded().astype(jnp.float32)
        n = data.n
        D = X.shape[1]
        C = Y.shape[1]
        w = self.mixture_weight
        mask = np.asarray(data.mask())

        class_of = np.asarray(jnp.argmax(Y, axis=1))[: n]
        counts = np.bincount(class_of, minlength=C).astype(np.float64)
        if (counts == 0).any():
            raise ValueError("every class needs at least one example")

        pop_mean = np.asarray(
            jnp.sum(X.astype(jnp.float32) * data.mask()[:, None], axis=0)
        ) / n
        # per-class mean and joint feature mean (C, D)
        onehot = np.zeros((X.shape[0], C), np.float32)
        onehot[np.arange(n), class_of] = 1.0
        class_sums = np.asarray(_f32_mm(jnp.asarray(onehot).T, X))
        class_means = class_sums / counts[:, None]
        jfm = class_means * w + pop_mean[None, :] * (1.0 - w)
        joint_label_mean = (
            2.0 * w + 2.0 * (1.0 - w) * counts / n - 1.0
        ).astype(np.float32)

        blocks = [
            (s, min(s + self.block_size, D) - s)
            for s in range(0, D, self.block_size)
        ]
        W = np.zeros((D, C), np.float32)
        neg_wt = (1.0 - w) / n
        Y_np = np.asarray(Y)

        for c in range(C):
            B = np.full(X.shape[0], neg_wt, np.float32) * mask
            B[np.arange(n)[class_of == c]] += w / counts[c]
            Bj = jnp.asarray(B)
            y_zm = jnp.asarray(
                (Y_np[:, c] - joint_label_mean[c]) * mask
            )
            res = jnp.zeros((X.shape[0], 1), jnp.float32)
            Wb = {s: jnp.zeros((wd, 1), jnp.float32) for s, wd in blocks}
            aTa = {s: jnp.zeros((wd, wd), jnp.float32) for s, wd in blocks}
            mu_bs = {
                s: jnp.asarray(jfm[c, s : s + wd]) for s, wd in blocks
            }
            lam_eyes = {
                wd: self.lam * jnp.eye(wd, dtype=jnp.float32)
                for _, wd in blocks
            }
            for it in range(self.num_iter):
                for s, wd in blocks:
                    Wb[s], res, aTa[s] = _rwls_block_step(
                        X, mu_bs[s], Bj, y_zm, res, Wb[s], aTa[s],
                        lam_eyes[wd], s, width=wd, first_pass=(it == 0),
                    )
            W[:, c] = np.concatenate(
                [np.asarray(Wb[s])[:, 0] for s, _ in blocks]
            )

        W = jnp.asarray(W)
        intercept = jnp.asarray(joint_label_mean) - jnp.einsum(
            "cd,dc->c", jnp.asarray(jfm, jnp.float32), W
        )
        return BlockLinearMapper(
            W, self.block_size, explicit_intercept=intercept
        )
