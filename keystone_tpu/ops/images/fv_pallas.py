"""The statistics of a diagonal GMM's posteriors as a Pallas TPU kernel.

Reference native path: nodes/images/external/FisherVector.scala:17 →
src/main/cpp/EncEval.cxx:19 (enceval `fisher<float>::compute`), the C++
implementation the reference switches to for k >= 32
(nodes/images/FisherVector.scala:84-94), and the E-step of
GaussianMixtureModelEstimator.scala's EM: both are one pass over the
descriptors that never needs the (m, k) posterior matrix whole.

Descriptors stay in the layout every node hands them over in, (d, m)
with the descriptors on the lanes, a batch of such matrices in front. A
grid step takes a tile of T descriptors of one matrix:

    z      = [x²; x; 1]                          (2d + 8, T) in VMEM
    logits = A z,  A = [−½/σ² | μ/σ² | c]        (k, T)      MXU
    q      = thresholded softmax over k          (k, T)      VPU
    S     += z qᵀ                                (2d + 8, k) MXU

so the constant of each word rides on the row of ones, and that row's
line of S is Σq. S and the tile-wise sums of log Σ_k exp(logits) (the EM's
cost) accumulate in output blocks revisited along the descriptor axis.
Every product is float32 at ``Precision.HIGHEST``. Nothing is padded or
transposed outside the kernel: the last tile's columns past m are masked
inside it.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.images.pallas_kernels import auto_interpret

TILE_M = 1024  # descriptors a grid step: the (k, T) posteriors stay in VMEM
ONES_ROWS = 8  # the row of ones, padded to a sublane tile

_HP = jax.lax.Precision.HIGHEST


def _stats_kernel(m_ref, thresh_ref, x_ref, a_ref, s_ref, lse_ref, *,
                  tile: int, hard: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        lse_ref[...] = jnp.zeros_like(lse_ref)

    col = j * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    valid = col < m_ref[0]
    x = jnp.where(valid, x_ref[0], 0.0)  # (d, T); past m the block is junk
    ones = jnp.broadcast_to(valid.astype(jnp.float32), (ONES_ROWS, tile))
    z = jnp.concatenate([x * x, x, ones], axis=0)  # (2d + 8, T)
    logits = jnp.dot(a_ref[...], z, preferred_element_type=jnp.float32,
                     precision=_HP)  # (k, T)
    top = jnp.max(logits, axis=0, keepdims=True)
    if hard:  # the nearest word takes the descriptor whole (k-means)
        q = jnp.where(logits >= top, 1.0, 0.0)
    else:
        e = jnp.exp(logits - top)
        total = jnp.sum(e, axis=0, keepdims=True)
        lse_ref[0] += jnp.where(valid, top + jnp.log(total), 0.0)
        q = e / total
        # aggressive posterior thresholding, as GaussianMixtureModel
        q = jnp.where(q > thresh_ref[0], q, 0.0)
    q = q / jnp.sum(q, axis=0, keepdims=True)
    q = jnp.where(valid, q, 0.0)
    s_ref[0] += jax.lax.dot_general(
        z, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HP,
    )  # (2d + 8, k)


def word_matrix(means, variances, weights):
    """A = [−½/σ² | μ/σ² | c 0 …] (k, 2d + 8): ``A [x²; x; 1]`` is
    log w_k + log N(x; μ_k, σ_k²) for every word k."""
    k = means.shape[1]
    const = (
        jnp.log(weights)
        - 0.5 * jnp.sum(jnp.log(2.0 * np.pi * variances), axis=0)
        - 0.5 * jnp.sum(means * means / variances, axis=0)
    )
    return jnp.concatenate(
        [(-0.5 / variances).T, (means / variances).T, const[:, None],
         jnp.zeros((k, ONES_ROWS - 1), jnp.float32)], axis=1,
    ).astype(jnp.float32)


def gmm_stats(
    x, means, variances, weights, weight_threshold=1e-4, *,
    hard: bool = False, tile: int = TILE_M,
    interpret: Optional[bool] = None,
):
    """x: (b, d, m) descriptor matrices; the GMM as (d, k), (d, k), (k,).
    Returns the sums over each matrix's m descriptors, not divided by m:
    s0 (b, k) = Σ q, s1 (b, d, k) = Σ x q, s2 (b, d, k) = Σ x² q with q the
    thresholded posteriors, and lse (b,) = Σ log Σ_k w_k N(x; k) (zeros
    with ``hard``, where q is the indicator of the likeliest word).
    ``interpret=None`` follows the backend (``auto_interpret``). Not
    jitted itself: XLA names the kernel's custom call after the innermost
    scope of the program that calls it (``fv.stats``, ``gmm.estep``,
    ``gmm.init``), and the benchmark's metrics find it by that name."""
    b, d, m = x.shape
    k = means.shape[1]
    rows = 2 * d + ONES_ROWS
    tile = min(tile, m)  # a block as long as the axis is always legal
    s, lse = pl.pallas_call(
        partial(_stats_kernel, tile=tile, hard=hard),
        grid=(b, -(-m // tile)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, tile), lambda i, j: (i, 0, j)),
            pl.BlockSpec((k, rows), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, rows, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, tile), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=auto_interpret(interpret),
    )(
        jnp.asarray([m], jnp.int32),
        jnp.asarray([weight_threshold], jnp.float32),
        x.astype(jnp.float32),
        word_matrix(means, variances, weights),
    )
    return (s[:, 2 * d], s[:, d:2 * d], s[:, :d], jnp.sum(lse, axis=(1, 2)))


def fisher_vector_stats_pallas(
    x, means, variances, weights, weight_threshold=1e-4, *,
    interpret: Optional[bool] = None,
):
    """x: (d, m) descriptors -> (s0 (k,), s1 (d, k), s2 (d, k)), each
    divided by m (the FisherVector.scala:33-41 statistics, with the
    GMM's posterior thresholding applied)."""
    s0, s1, s2, _ = gmm_stats(
        x[None], means, variances, weights, weight_threshold,
        interpret=interpret,
    )
    inv_m = 1.0 / x.shape[1]
    return s0[0] * inv_m, s1[0] * inv_m, s2[0] * inv_m
