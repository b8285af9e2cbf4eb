"""Pallas TPU kernels for the flagship featurize hot loops.

The SIFT and LCS extractors both reduce their heavy stage to a GEMM
sandwich ``Aᵀ · Z · B`` over a stack of small planes (sift.py
``_sampling_matrix`` / lcs.py ``_lcs_sampling_matrix`` document the
reformulation) — exactly the shape the MXU wants, but as plain XLA the
plane stack round-trips HBM between the binning that produces it and
the two matmuls that consume it. These kernels fuse that seam, the
same VMEM-residency move ``fv_pallas`` makes for the FV statistics:

- ``sift_bin_sample``: trilinear orientation binning (the vl_dsift
  gradient→8-plane scatter) fused with the two sampling-matrix GEMMs.
  The grid walks the 8 orientations; each step materializes ONE
  (H, W) orientation plane in VMEM from the gradient magnitude/angle
  fields and contracts it down to (M, N) on the MXU — the (8, H, W)
  plane stack never exists in HBM.
- ``plane_sandwich``: the plain sandwich for LCS box-mean/variance
  extraction (image and image² share the chain as stacked planes).

Both run under ``interpret=True`` on the CPU backend
(``auto_interpret``), so CPU tier-1 exercises the exact kernel
dataflow, and Mosaic-compiled on TPU; both batch cleanly
under ``vmap`` (pallas_call's batching rule folds the batch into the
grid), which is how the bucket-vmapped extractors drive them. Dots
pin f32 HIGHEST precision — the extractors' parity tolerances
(1e-4 vs the independent numpy translations) were set against it.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_ORIENTATIONS = 8

_HP = jax.lax.Precision.HIGHEST


def auto_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` flag: ``None`` selects the Mosaic
    compile path on ``tpu`` and the Pallas interpreter on ``cpu``; any
    other backend raises — these are TPU kernels, and interpreting
    them silently on an unknown accelerator would hide the device.
    Resolved at trace time, so a jitted caller bakes the choice into
    its program like any other static."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels compile on 'tpu' and interpret on 'cpu'; "
        f"the default backend is {backend!r} — pass interpret= "
        "explicitly to run them there"
    )


def _sift_bin_sample_kernel(
    mag_ref, orient_ref, ayt_ref, ax_ref, out_ref
):
    t = pl.program_id(0)
    tq = orient_ref[:]  # continuous orientation in [0, 8)
    b0f = jnp.floor(tq)
    frac = tq - b0f
    b0 = b0f.astype(jnp.int32) % NUM_ORIENTATIONS
    b1 = (b0 + 1) % NUM_ORIENTATIONS
    # this orientation's trilinear share of the gradient magnitude —
    # the vl_dsift bilinear-over-orientation binning, one plane at a
    # time so the full (8, H, W) stack never leaves VMEM
    plane = mag_ref[:] * (
        jnp.where(b0 == t, 1.0 - frac, 0.0)
        + jnp.where(b1 == t, frac, 0.0)
    )
    t1 = jnp.dot(ayt_ref[:], plane,
                 preferred_element_type=jnp.float32, precision=_HP)
    out_ref[0] = jnp.dot(t1, ax_ref[:],
                         preferred_element_type=jnp.float32,
                         precision=_HP)


def sift_bin_sample(
    mag: jnp.ndarray,
    orient: jnp.ndarray,
    ayt: jnp.ndarray,
    ax: jnp.ndarray,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused trilinear orientation binning + spatial-binning GEMMs.

    ``mag``/``orient``: (H, W) gradient magnitude and continuous
    orientation (angle / 2π · 8); ``ayt``: (M, H) transposed y-axis
    sampling matrix; ``ax``: (W, N) x-axis sampling matrix. Returns
    (8, M, N) — orientation t's plane contracted through both
    sampling operators, bit-for-bit the one_hot+einsum formulation it
    replaces."""
    H, W = mag.shape
    M, N = ayt.shape[0], ax.shape[1]
    return pl.pallas_call(
        _sift_bin_sample_kernel,
        grid=(NUM_ORIENTATIONS,),
        in_specs=[
            pl.BlockSpec((H, W), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((H, W), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((M, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((W, N), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, M, N), lambda t: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (NUM_ORIENTATIONS, M, N), jnp.float32
        ),
        interpret=auto_interpret(interpret),
    )(
        mag.astype(jnp.float32),
        orient.astype(jnp.float32),
        ayt.astype(jnp.float32),
        ax.astype(jnp.float32),
    )


def _plane_sandwich_kernel(plane_ref, at_ref, b_ref, out_ref):
    t1 = jnp.dot(at_ref[:], plane_ref[0],
                 preferred_element_type=jnp.float32, precision=_HP)
    out_ref[0] = jnp.dot(t1, b_ref[:],
                         preferred_element_type=jnp.float32,
                         precision=_HP)


def plane_sandwich(
    planes: jnp.ndarray,
    at: jnp.ndarray,
    b: jnp.ndarray,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(P, M, N) GEMM sandwich ``out[p] = at @ planes[p] @ b`` — the
    LCS box-filter→sample stage over the stacked image/image² channel
    planes (``at``: (M, X) transposed x-axis sampling matrix, ``b``:
    (Y, N) y-axis one). The grid walks planes; each stays VMEM-resident
    between its two dots."""
    P, H, W = planes.shape
    M, N = at.shape[0], b.shape[1]
    return pl.pallas_call(
        _plane_sandwich_kernel,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda p: (p, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((M, H), lambda p: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((W, N), lambda p: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, M, N), lambda p: (p, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((P, M, N), jnp.float32),
        interpret=auto_interpret(interpret),
    )(
        planes.astype(jnp.float32),
        at.astype(jnp.float32),
        b.astype(jnp.float32),
    )


__all__ = ["auto_interpret", "sift_bin_sample", "plane_sandwich"]
