"""Kernel ridge regression by block Gauss-Seidel on the dual
(arXiv:1602.05310), with RBF kernel generation.

Reference: nodes/learning/KernelGenerator.scala:18-206 (GaussianKernel
column blocks via broadcast + per-partition matmul),
KernelMatrix.scala:17,50 (lazy column-block view w/ caching),
KernelRidgeRegression.scala:37,86-235 (per epoch & column block:
materialize K(:,B), treeReduce K_Bᵀ·W, driver solve of
(K_BB + λI) W_B = Y_B − K_BᵀW + K_BBᵀW_B_old, broadcast + scatter model
update, lineage checkpoint every 25 blocks),
KernelBlockLinearMapper.scala:28 (test-time blockwise K_test(:,B)·W_B
accumulation).

TPU-native: the kernel column block is one fused jitted expression
(‖x‖² + ‖x_B‖² − 2·X X_Bᵀ → exp), the b×k residual contraction psums over
the sharded example axis, the small (b, b) solve goes to the host in f64
(hostsolve.py), and the model update is a dynamic_update_slice — no
broadcast variables. The reference's every-25-blocks lineage checkpoint
becomes a cadenced atomic host snapshot of the model that ``fit`` resumes
from after preemption (``checkpoint_path``; utils/checkpoint.py).

Observability: host spans ``solver.krr.prep`` (arrays, the train set's
norms, the block schedule), ``solver.krr.dispatch`` (the fit's device
programs enqueued; on the per-block paths its host loop, with
``solver.krr.kernel_block`` / ``.residual`` / ``.host_solve`` /
``.update`` inside it where ``solve="host"``) and
``solver.krr.converged`` (the read of "is the model finite" that the
fit waits on: above ``block_ls._FALLBACK_MAX_WIDTH`` columns a
Cholesky breakdown has no fall-back and shows as a non-finite model); on
the device ``jax.named_scope`` names ``krr.kernel_block`` /
``krr.residual`` / ``krr.solve`` / ``krr.update``; counters
``keystone_solver_krr_fits_total``, ``_krr_block_steps_total``,
``_krr_kernel_blocks_total`` (column blocks really generated: a cached
fit generates each once) and ``_krr_path_total{path}`` (``scan`` |
``cached`` | ``block`` | ``host``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.learning.block_ls import (
    _f32_mm,
    _psd_solve_device,
    _psd_solve_with_factor,
)
from keystone_tpu.ops.learning.hostsolve import psd_solve_host
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.checkpoint import (
    LoopCheckpointer,
    data_probe,
    two_level_schedule,
)
from keystone_tpu.workflow.api import Estimator, LabelEstimator, Transformer


def _cross_mm_x3(A, B):
    """A·Bᵀ for f32 operands with XLA's 3-pass bf16 algorithm — ~2×
    faster than the 6-pass HIGHEST decomposition at ~1.5e-5 relative
    error, which the RBF distance tolerates: the kernel's sensitivity is
    γ·|d² error| and γ·1.5e-5·‖x‖² ≪ any solver tolerance here."""
    return jax.lax.dot_general(
        A, B, (((1,), (1,)), ((), ())),
        precision=jax.lax.DotAlgorithmPreset.BF16_BF16_F32_X3,
    )


def _rbf_block_body(X, X_norms, gamma, mask, start, width):
    """K(:, B) for a contiguous train block: exp(−γ(‖x‖²+‖x_B‖²−2x·x_B)).
    Pad rows AND pad columns are zeroed — exp(·) of a zero pad vector is
    nonzero and would pollute the Gauss-Seidel solves."""
    with jax.named_scope("krr.kernel_block"):
        Xb = jax.lax.dynamic_slice_in_dim(X, start, width, axis=0)
        nb = jax.lax.dynamic_slice_in_dim(X_norms, start, width, axis=0)
        mask_b = jax.lax.dynamic_slice_in_dim(mask, start, width, axis=0)
        d2 = X_norms[:, None] + nb[None, :] - 2.0 * _cross_mm_x3(X, Xb)
        K = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
        return K * mask[:, None] * mask_b[None, :]


@jax.jit
def _row_norms(X):
    """‖x‖² of every row in one pass: eager, ``X ** 2`` is a second copy
    of the train set (2 GB at 125,000 x 4,096) before it is summed."""
    X = X.astype(jnp.float32)
    return jnp.sum(X * X, axis=1)


@partial(jax.jit, static_argnames=("width",))
def _rbf_block(X, X_norms, gamma, mask, start, *, width):
    return _rbf_block_body(X, X_norms, gamma, mask, start, width)


@dataclasses.dataclass(eq=False)
class GaussianKernelTransformer(Transformer):
    """Holds the train set; produces kernel blocks against it (reference:
    KernelGenerator.scala:49).

    Precision note (ADVICE r4): the blocked cross term uses XLA's
    3-pass bf16 GEMM (``_cross_mm_x3``, ~1.5e-5 relative error), so the
    absolute kernel error scales as γ·1.5e-5·‖x‖². With normalized
    features and the small γ the apps use (γ·‖x‖² ≲ 10) that is ≤1e-4
    on kernel entries — far below solver tolerance; with LARGE
    γ·‖x‖² (unnormalized features) kernel entries lose accuracy
    proportionally. Normalize features (NormalizeRows) or scale γ
    down accordingly."""

    train_X: Any  # (n_pad, d) device array, pad rows zero
    n_train: int
    gamma: float
    train_mask: Any = None

    def __post_init__(self):
        if self.train_mask is None:
            self.train_mask = (
                jnp.arange(self.train_X.shape[0]) < self.n_train
            ).astype(jnp.float32)
        self._norms = _row_norms(self.train_X)

    def apply(self, x):
        """kernel row of a single test point vs the whole train set."""
        d2 = (
            jnp.sum(x * x)
            + self._norms
            - 2.0 * (self.train_X @ x).astype(jnp.float32)
        )
        return jnp.exp(-self.gamma * jnp.maximum(d2, 0.0)) * self.train_mask

    def apply_batch(self, ds: Dataset) -> Dataset:
        """Kernel rows vs the train set as a Dataset (pipeline contract);
        KRR uses ``kernel_matrix`` for the lazy block view instead."""
        ds = ds.to_array_mode()
        km = self.kernel_matrix(ds)
        n_pad = self.train_X.shape[0]
        return Dataset.from_array(km.block(0, n_pad), n=ds.n)

    def kernel_matrix(self, ds: Dataset) -> "KernelMatrix":
        ds = ds.to_array_mode()
        return KernelMatrix(self, ds)

    def train_block(self, start: int, width: int) -> jnp.ndarray:
        return _rbf_block(
            self.train_X, self._norms, self.gamma, self.train_mask,
            start, width=width,
        )


@partial(jax.jit, static_argnames=("width",))
def _rbf_cross_block(Xt, Xt_norms, train_X, train_norms, gamma, mask_t,
                     train_mask, start, *, width):
    Xb = jax.lax.dynamic_slice_in_dim(train_X, start, width, axis=0)
    nb = jax.lax.dynamic_slice_in_dim(train_norms, start, width, axis=0)
    mask_b = jax.lax.dynamic_slice_in_dim(train_mask, start, width, axis=0)
    d2 = Xt_norms[:, None] + nb[None, :] - 2.0 * _cross_mm_x3(Xt, Xb)
    K = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
    return K * mask_t[:, None] * mask_b[None, :]


class KernelMatrix:
    """Lazy column-block view of K(test, train) with optional block cache
    (reference: KernelMatrix.scala:17 / BlockKernelMatrix:50)."""

    def __init__(self, transformer: GaussianKernelTransformer, ds: Dataset,
                 cache_blocks: bool = False):
        self.transformer = transformer
        self.ds = ds
        self._X = ds.padded().astype(jnp.float32)
        self._norms = _row_norms(self._X)
        self._mask = ds.mask()
        self.cache_blocks = cache_blocks
        self._cache: Dict[tuple, jnp.ndarray] = {}

    def block(self, start: int, width: int) -> jnp.ndarray:
        key = (start, width)
        if key in self._cache:
            return self._cache[key]
        out = _rbf_cross_block(
            self._X, self._norms, self.transformer.train_X,
            self.transformer._norms, self.transformer.gamma, self._mask,
            self.transformer.train_mask, start, width=width,
        )
        if self.cache_blocks:
            self._cache[key] = out
        return out

    def diag_block(self, start: int, width: int) -> jnp.ndarray:
        """K_BB for a train-set kernel matrix (square view only —
        dynamic_slice would silently clamp on a rectangular test-vs-train
        matrix)."""
        if self._X.shape[0] < start + width:
            raise ValueError(
                "diag_block requires a square (train) kernel matrix"
            )
        K = self.block(start, width)
        return jax.lax.dynamic_slice_in_dim(K, start, width, axis=0)

    def unpersist(self, start: int, width: int) -> None:
        self._cache.pop((start, width), None)


@dataclasses.dataclass(eq=False)
class GaussianKernelGenerator(Estimator):
    """fit(data) -> GaussianKernelTransformer (reference:
    KernelGenerator.scala:18)."""

    gamma: float

    def fit(self, data: Dataset) -> GaussianKernelTransformer:
        ds = data.to_array_mode()
        X = ds.padded().astype(jnp.float32)
        if ds.n < ds.padded_n:
            # zero the pad rows; with none, the transformer (and the
            # fitted model) holds the array it was given and no copy
            X = X * ds.mask()[:, None]
        return GaussianKernelTransformer(X, ds.n, self.gamma, ds.mask())


@partial(jax.jit, static_argnames=("width",))
def _krr_residual(K_block, W, start, *, width):
    """K_Bᵀ W and K_BB from the materialized column block."""
    resid = _f32_mm(K_block.T, W)
    K_bb = jax.lax.dynamic_slice_in_dim(K_block, start, width, axis=0)
    return resid, K_bb


@partial(jax.jit, static_argnames=("width",), donate_argnums=(0,))
def _krr_update_model(W, Wb_new, start, *, width):
    return jax.lax.dynamic_update_slice_in_dim(W, Wb_new, start, axis=0)


def _krr_block_body(X, X_norms, gamma, mask, W, Y, start, lam, width):
    """One whole Gauss-Seidel block update as a single device program:
    materialize K(:, B), form the residual rhs, solve (K_BB + λI) on
    device (f32 Cholesky + refinement, block_ls._psd_solve_device), and
    scatter the block model — the reference's materialize → treeReduce →
    driver-solve → broadcast round trip (KernelRidgeRegression.scala:
    86-235) with zero host synchronization."""
    K_block = _rbf_block_body(X, X_norms, gamma, mask, start, width)
    with jax.named_scope("krr.residual"):
        # contract the example axis without a .T relayout of the n×b block
        resid = jax.lax.dot_general(
            K_block, W, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        K_bb = jax.lax.dynamic_slice_in_dim(K_block, start, width, axis=0)
        Wb_old = jax.lax.dynamic_slice_in_dim(W, start, width, axis=0)
        y_b = jax.lax.dynamic_slice_in_dim(Y, start, width, axis=0)
        rhs = y_b - (resid - _f32_mm(K_bb.T, Wb_old))
    # one refinement step: each extra step is a triangular-solve pair
    # (~3 ms at b=4096), and Gauss-Seidel tolerates per-block solves at
    # f32+1-refine accuracy (validated against the host-f64 path by
    # tests/ops/test_kernel.py)
    with jax.named_scope("krr.solve"):
        Wb_new = _psd_solve_device(K_bb, rhs, lam, refine=1)
    with jax.named_scope("krr.update"):
        return jax.lax.dynamic_update_slice_in_dim(W, Wb_new, start, axis=0)


@partial(jax.jit, static_argnames=("width",), donate_argnums=(4,))
def _krr_block_step(X, X_norms, gamma, mask, W, Y, start, lam, *, width):
    return _krr_block_body(X, X_norms, gamma, mask, W, Y, start, lam,
                           width)


@partial(jax.jit, static_argnames=("width",), donate_argnums=(4,))
def _krr_cached_epoch_scan(X, X_norms, gamma, mask, W, Y,
                           block_idx, lam, *, width):
    """Gauss-Seidel with the kernel matrix CACHED in HBM — the
    reference's ``cacheKernel`` mode (KernelMatrix.scala:50,
    BlockKernelMatrix). Three stages, one dispatch:

    1. build all column blocks once (scan, stacked ys) — multi-epoch
       fits stop regenerating K(:, B) every sweep (the regeneration
       GEMM is ~70 ms/epoch at the bench shape, the dominant per-epoch
       cost);
    2. factorize ALL diagonal blocks as one batched Cholesky — the 12
       sequential 4096² factorizations (~26 ms measured) become one
       batched kernel (~10 ms): across-batch panels run in parallel on
       the MXU, and the factor bank is reused by every later epoch;
    3. sweep: per block, residual contraction + two triangular-solve
       pairs (solve + 1 refinement) against the prebuilt factor.

    Memory: the cache holds n_pad² + nb·b² f32 — ``fit`` gates this
    path on the measured device budget and falls back to the
    regenerate-per-block scan (``_krr_epoch_scan``)."""
    n_pad = X.shape[0]
    nb = n_pad // width
    eye = jnp.eye(width, dtype=jnp.float32)
    hp = jax.lax.Precision.HIGHEST

    def build(c, i):
        s = i * width
        Kb = _rbf_block_body(X, X_norms, gamma, mask, s, width)
        Ab = jax.lax.dynamic_slice_in_dim(Kb, s, width, axis=0) + lam * eye
        return c, (Kb, Ab)

    _, (Kcols, Ab) = jax.lax.scan(build, jnp.float32(0), jnp.arange(nb))
    with jax.named_scope("krr.solve"):
        Lb = jnp.linalg.cholesky(Ab)

    def step(W, bi):
        s = bi * width
        with jax.named_scope("krr.residual"):
            Kcol = jax.lax.dynamic_index_in_dim(Kcols, bi, 0, keepdims=False)
            resid = jax.lax.dot_general(
                Kcol, W, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hp,
            )
            K_bb = jax.lax.dynamic_slice_in_dim(Kcol, s, width, axis=0)
            Wb_old = jax.lax.dynamic_slice_in_dim(W, s, width, axis=0)
            y_b = jax.lax.dynamic_slice_in_dim(Y, s, width, axis=0)
            rhs = y_b - (resid - _f32_mm(K_bb.T, Wb_old))
        with jax.named_scope("krr.solve"):
            L = jax.lax.dynamic_index_in_dim(Lb, bi, 0, keepdims=False)
            # refine=1 matches the uncached scan's _psd_solve_device call
            # (validated by the same f64-parity tests); the helper carries
            # the breakdown fall-back and its width gating
            Wb_new, _ = _psd_solve_with_factor(
                K_bb + lam * eye, L, rhs, refine=1
            )
        with jax.named_scope("krr.update"):
            return jax.lax.dynamic_update_slice_in_dim(
                W, Wb_new, s, axis=0
            ), None

    W, _ = jax.lax.scan(step, W, block_idx)
    return W


@partial(jax.jit, static_argnames=("width",), donate_argnums=(4,))
def _krr_epoch_scan(X, X_norms, gamma, mask, W, Y, starts, lam, *, width):
    """A whole epoch (or several) of Gauss-Seidel block updates as ONE
    scanned device program: one dispatch per epoch instead of one per
    block."""

    def step(W, start):
        return _krr_block_body(
            X, X_norms, gamma, mask, W, Y, start, lam, width
        ), None

    W, _ = jax.lax.scan(step, W, starts)
    return W


def _count_krr_fit(path: str):
    """Count one fit started on ``path``; the callable it returns counts
    block steps of that fit and the column blocks generated for them."""
    reg = get_global_registry()
    reg.counter(
        "keystone_solver_krr_fits_total",
        "kernel ridge regression fits started",
    ).inc()
    reg.counter(
        "keystone_solver_krr_path_total",
        "kernel ridge regression fits by the path taken",
        labelnames=("path",),
    ).inc((path,))
    block_steps = reg.counter(
        "keystone_solver_krr_block_steps_total",
        "Gauss-Seidel block updates of the dual model",
    )
    generated = reg.counter(
        "keystone_solver_krr_kernel_blocks_total",
        "kernel column blocks generated on the device",
    )

    def count_step(steps: int = 1, kernel_blocks: int = 1) -> None:
        block_steps.inc(by=steps)
        generated.inc(by=kernel_blocks)

    return count_step


@dataclasses.dataclass(eq=False)
class KernelBlockLinearMapper(Transformer):
    """Test-time apply: accumulate K_test(:, B) · W_B over blocks
    (reference: KernelBlockLinearMapper.scala:28)."""

    model: Any  # (n_train_pad, k)
    block_size: int
    kernel_transformer: GaussianKernelTransformer
    n_train: int

    def apply(self, x):
        k_row = self.kernel_transformer.apply(x)
        return k_row @ self.model

    def apply_batch(self, ds: Dataset) -> Dataset:
        ds = ds.to_array_mode()
        km = self.kernel_transformer.kernel_matrix(ds)
        n_pad = self.kernel_transformer.train_X.shape[0]
        out = jnp.zeros(
            (ds.padded_n, self.model.shape[1]), jnp.float32
        )
        for start in range(0, n_pad, self.block_size):
            width = min(self.block_size, n_pad - start)
            Kb = km.block(start, width)
            Wb = jax.lax.dynamic_slice_in_dim(
                self.model, start, width, axis=0
            )
            out = out + _f32_mm(Kb, Wb)
        return Dataset.from_array(out, n=ds.n)


@dataclasses.dataclass(eq=False)
class KernelRidgeRegression(LabelEstimator):
    """(K + λI) W = Y via column-block Gauss-Seidel (reference:
    KernelRidgeRegression.scala:37)."""

    kernel_generator: GaussianKernelGenerator
    lam: float
    block_size: int
    num_epochs: int
    block_permuter: Optional[int] = None
    solve: str = "device"  # "device": f32 Cholesky + iterative refinement
    # in the dispatch stream (same discipline as BlockLS — a host solve
    # costs a sync per block) |
    # "host": f64 LAPACK per block for pathological conditioning
    checkpoint_path: Optional[str] = None  # periodic model snapshot every
    # ``checkpoint_every`` block solves; a re-run with the same path
    # resumes at the last completed block (reference checkpoints lineage
    # every 25 blocks: KernelRidgeRegression.scala:200-210)
    checkpoint_every: int = 25
    block_callback: Optional[Any] = None  # called with a running count
    # after each completed block solve
    cache_kernel: Optional[bool] = None  # cache the whole train kernel
    # matrix in HBM + batch-factorize the diagonal blocks (the
    # reference's cacheKernel mode, KernelMatrix.scala:50). None = auto:
    # on when the cache fits the device budget AND num_epochs > 1 —
    # measured on the v5e at the bench shape (49k × 1024, b=4096):
    # marginal epoch cost drops 142 → 40 ms device (epoch 2+ skips
    # kernel regeneration; diagonal factors come from one batched
    # Cholesky bank), 1.79× at 3 epochs, but the one-epoch fit pays
    # ~+14 ms of cache-build overhead. Same math (refine=1 Cholesky,
    # ridged fall-back; rel diff 6e-6), validated by the same parity tests.

    def _epoch_order(self, epoch: int, n_blocks: int) -> List[int]:
        """Block order for an epoch, seeded per (permuter, epoch) so a
        resumed fit replays the identical schedule.

        NOTE: this changed the schedule for a given ``block_permuter``
        relative to the pre-checkpointing implementation (one RNG stream
        across epochs); models fit with the same seed before/after differ
        numerically (both are valid Gauss-Seidel orders)."""
        order = list(range(n_blocks))
        if self.block_permuter is not None:
            np.random.default_rng(
                (self.block_permuter, epoch)
            ).shuffle(order)
        return order

    def fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        if self.solve not in ("device", "host"):
            raise ValueError(f"solve must be 'device' or 'host', got {self.solve!r}")
        with span("solver.krr.prep"):
            data = data.to_array_mode()
            labels = labels.to_array_mode()
            transformer = self.kernel_generator.fit(data)
            X = transformer.train_X
            n = data.n
            n_pad = X.shape[0]
            Y = labels.padded().astype(jnp.float32)
            k = Y.shape[1]
            blocks = [
                (s, min(s + self.block_size, n_pad) - s)
                for s in range(0, n_pad, self.block_size)
            ]
            W = jnp.zeros((n_pad, k), jnp.float32)

        ckpt = None
        start_epoch, start_pos = 0, 0
        if self.checkpoint_path is not None:
            # n_pad is stamped too: the snapshot W and block layout are
            # n_pad-shaped, and n_pad varies with mesh shard count
            fp = (
                f"krr bs={self.block_size} ep={self.num_epochs} "
                f"lam={self.lam} gamma={self.kernel_generator.gamma} "
                f"perm={self.block_permuter} n={n} n_pad={n_pad} k={k} "
                f"solve={self.solve} "
                f"probe={data_probe(X, Y)}"
            )
            ckpt = LoopCheckpointer(self.checkpoint_path,
                                    self.checkpoint_every, fingerprint=fp)
            state = ckpt.load()
            if state is not None:
                W = jnp.asarray(state["W"], jnp.float32)
                start_epoch = int(state["epoch"])
                start_pos = int(state["pos"])

        one_program = (
            self.solve == "device"
            and ckpt is None
            and self.block_callback is None
            and len({wd for _, wd in blocks}) == 1
        )
        if one_program:
            W = self._fit_one_program(transformer, W, Y, blocks)
        else:
            W = self._fit_block_loop(
                transformer, W, Y, blocks, ckpt, start_epoch, start_pos
            )
        with span("solver.krr.converged"):
            finite = bool(jnp.all(jnp.isfinite(W)))
        if not finite:
            raise FloatingPointError(
                "KernelRidgeRegression: the fitted model is not finite — "
                f"a Cholesky factorisation of K_BB + {self.lam}·I broke "
                "down in float32 (blocks wider than "
                "block_ls._FALLBACK_MAX_WIDTH have no fall-back); "
                "raise lam or use solve='host'"
            )
        return KernelBlockLinearMapper(W, self.block_size, transformer, n)

    def _fit_one_program(self, transformer, W, Y, blocks):
        """Every epoch's whole block schedule as one scanned program,
        one dispatch for the entire fit."""
        n_pad = transformer.train_X.shape[0]
        order = [
            i
            for epoch in range(self.num_epochs)
            for i in self._epoch_order(epoch, len(blocks))
        ]
        width = blocks[0][1]
        use_cached = self.cache_kernel
        if use_cached is None:
            from keystone_tpu.ops.learning.weighted_ls import (
                _device_memory_limit,
            )
            # cache bytes: stacked column blocks + factor bank +
            # one (n_pad, b) transient; leave room for X/W/Y and
            # the fall-back factor's workspace
            cache_bytes = 4 * (
                n_pad * n_pad
                + len(blocks) * width * width
                + n_pad * width
            )
            use_cached = (
                self.num_epochs > 1
                and cache_bytes <= 0.6 * _device_memory_limit()
            )
        count_step = _count_krr_fit("cached" if use_cached else "scan")
        count_step(
            steps=len(order),
            kernel_blocks=len(blocks) if use_cached else len(order),
        )
        with span("solver.krr.dispatch", blocks=len(order)):
            if use_cached:
                return _krr_cached_epoch_scan(
                    transformer.train_X, transformer._norms,
                    transformer.gamma, transformer.train_mask,
                    W, Y, jnp.asarray(order, jnp.int32), self.lam,
                    width=width,
                )
            all_starts = jnp.asarray(
                [blocks[i][0] for i in order], jnp.int32
            )
            return _krr_epoch_scan(
                transformer.train_X, transformer._norms,
                transformer.gamma, transformer.train_mask,
                W, Y, all_starts, self.lam, width=width,
            )

    def _fit_block_loop(self, transformer, W, Y, blocks, ckpt,
                        start_epoch, start_pos):
        """One block update at a time from the host: host solves,
        checkpoint ticks, callbacks, ragged widths. K(:, B) is
        regenerated on each visit."""
        if self.cache_kernel:
            # the cached program is the single-dispatch scan
            import warnings

            warnings.warn(
                "cache_kernel=True has no effect with solve='host', "
                "checkpoint_path, block_callback, or non-uniform block "
                "widths — falling back to per-block kernel regeneration",
                stacklevel=3,
            )
        count_step = _count_krr_fit(
            "block" if self.solve == "device" else "host"
        )
        done = 0
        order, order_epoch = [], -1
        with span("solver.krr.dispatch"):
            for epoch, pos, nxt in two_level_schedule(
                self.num_epochs, len(blocks), (start_epoch, start_pos)
            ):
                if epoch != order_epoch:
                    order = self._epoch_order(epoch, len(blocks))
                    order_epoch = epoch
                s, wd = blocks[order[pos]]
                if self.solve == "device":
                    # whole block update — kernel block, residual, solve,
                    # model scatter — stays in the async dispatch stream
                    W = _krr_block_step(
                        transformer.train_X, transformer._norms,
                        transformer.gamma, transformer.train_mask,
                        W, Y, s, self.lam, width=wd,
                    )
                else:
                    W = self._host_block_step(transformer, W, Y, s, wd)
                count_step()
                done += 1
                if ckpt is not None:
                    ckpt.tick(lambda: {
                        "W": np.asarray(W), "epoch": nxt[0], "pos": nxt[1],
                    })
                if self.block_callback is not None:
                    self.block_callback(done)
        if ckpt is not None:
            ckpt.clear()
        return W

    def _host_block_step(self, transformer, W, Y, s, wd):
        """The reference's round trip: the column block and the residual
        on the device, the (b, b) system on the host in float64."""
        with span("solver.krr.kernel_block"):
            K_block = transformer.train_block(s, wd)  # (n_pad, b)
        with span("solver.krr.residual"):
            resid, K_bb = _krr_residual(K_block, W, s, width=wd)
            Wb_old = jax.lax.dynamic_slice_in_dim(W, s, wd, axis=0)
            y_b = jax.lax.dynamic_slice_in_dim(Y, s, wd, axis=0)
            rhs = y_b - (resid - _f32_mm(K_bb.T, Wb_old))
        # pad rows inside the block: K_bb row/col is zero there,
        # λI makes the system nonsingular, W stays 0 via rhs=0
        with span("solver.krr.host_solve"):
            Wb_new = jnp.asarray(
                psd_solve_host(K_bb, np.asarray(rhs), self.lam),
                jnp.float32,
            )
        with span("solver.krr.update"):
            return _krr_update_model(W, Wb_new, s, width=wd)
