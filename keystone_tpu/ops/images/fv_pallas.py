"""Fused Fisher-vector statistics as a Pallas TPU kernel.

Reference native path: nodes/images/external/FisherVector.scala:17 →
src/main/cpp/EncEval.cxx:19 (enceval `fisher<float>::compute`), the C++
implementation the reference switches to for k >= 32
(nodes/images/FisherVector.scala:84-94). The TPU equivalent of "native"
is a Pallas kernel that fuses the three matmuls and the softmax of the
FV statistics pass so the (m, k) posterior matrix is never written to
HBM:

    logits = -0.5 * X² @ (1/σ²) + X @ (μ/σ²) + c        (MXU)
    q      = softmax(logits, axis=-1)                    (VPU, in VMEM)
    s0    += Σ_rows q ;  s1 += Xᵀ q ;  s2 += (X²)ᵀ q     (MXU)

The grid walks descriptor chunks; s0/s1/s2 accumulate in revisited VMEM
output blocks. For the unfused baseline (and the k < 32 physical
choice) see fisher_vector.FisherVector.
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

TILE_M = 512  # descriptors per grid step; X chunk is TILE_M x d in VMEM


def _fv_stats_kernel(
    m_valid_ref, thresh_ref, x_ref, inv_var_ref, proj_ref, const_ref,
    s0_ref, s1_ref, s2_ref,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        s0_ref[:] = jnp.zeros_like(s0_ref)
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    x = x_ref[:]  # (TILE_M, d)
    x2 = x * x
    logits = (
        -0.5 * jnp.dot(x2, inv_var_ref[:],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        + jnp.dot(x, proj_ref[:], preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
        + const_ref[:]
    )  # (TILE_M, k)
    logits = logits - jnp.max(logits, axis=1, keepdims=True)
    q = jnp.exp(logits)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    # aggressive posterior thresholding + renormalize, matching
    # GaussianMixtureModel._posteriors (gmm.py:55-60)
    q = jnp.where(q > thresh_ref[0], q, 0.0)
    q = q / jnp.sum(q, axis=1, keepdims=True)

    # zero pad rows (global row index >= m_valid)
    rows = step * TILE_M + jax.lax.broadcasted_iota(
        jnp.int32, q.shape, 0
    )
    q = jnp.where(rows < m_valid_ref[0], q, 0.0)

    s0_ref[:] += jnp.sum(q, axis=0, keepdims=True)
    s1_ref[:] += jnp.dot(x.T, q, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
    s2_ref[:] += jnp.dot(x2.T, q, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("interpret",))
def fisher_vector_stats_pallas(
    x, means, variances, weights, weight_threshold=1e-4,
    *, interpret: Optional[bool] = None
):
    """x: (d, m) descriptors -> (s0 (k,), s1 (d, k), s2 (d, k)), each
    already divided by m (the FisherVector.scala:33-41 statistics, with
    the GMM's posterior thresholding applied). ``interpret=None``
    follows the backend: Mosaic-compiled on ``tpu``, the Pallas
    interpreter on ``cpu``, an error anywhere else
    (``pallas_kernels.auto_interpret``)."""
    interpret = auto_interpret(interpret)
    d, m = x.shape
    k = means.shape[1]
    inv_var = 1.0 / variances  # (d, k)
    proj = means / variances  # (d, k)
    const = (
        jnp.log(weights)[None, :]
        - 0.5 * jnp.sum(jnp.log(2.0 * np.pi * variances), axis=0)[None, :]
        - 0.5 * jnp.sum(means * proj, axis=0)[None, :]
    )  # (1, k)

    m_pad = max(((m + TILE_M - 1) // TILE_M) * TILE_M, TILE_M)
    xt = jnp.zeros((m_pad, d), jnp.float32).at[:m].set(
        x.T.astype(jnp.float32)
    )
    grid = m_pad // TILE_M

    s0, s1, s2 = pl.pallas_call(
        _fv_stats_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_M, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((d, k), jnp.float32),
            jax.ShapeDtypeStruct((d, k), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray([m], jnp.int32),
        jnp.asarray([weight_threshold], jnp.float32),
        xt,
        inv_var.astype(jnp.float32),
        proj.astype(jnp.float32),
        const.astype(jnp.float32),
    )
    inv_m = 1.0 / m
    return s0[0] * inv_m, s1 * inv_m, s2 * inv_m
