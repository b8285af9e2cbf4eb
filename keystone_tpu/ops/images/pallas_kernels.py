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
- ``conv_rectify_pool``: a Convolver's product fused with the
  SymmetricRectifier and a sum Pooler. The grid walks (image tile ×
  filter tile); each step makes one (positions, filter tile) slab of
  maps in VMEM on the MXU, rectifies it both ways on the VPU and sums
  it into its pooling windows — the (rows, X, Y, F) maps never exist
  in HBM, only the pooled sums do.

All run under ``interpret=True`` on the CPU backend
(``auto_interpret``), so CPU tier-1 exercises the exact kernel
dataflow, and Mosaic-compiled on TPU; the first two batch cleanly
under ``vmap`` (pallas_call's batching rule folds the batch into the
grid), which is how the bucket-vmapped extractors drive them. Dots
pin f32 HIGHEST precision — the extractors' parity tolerances
(1e-4 vs the independent numpy translations) were set against it.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

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


# conv_rectify_pool's tiles: images a grid step (their patches stay in
# VMEM while the filter tiles pass) and filters a grid step
CONV_IMAGE_TILE = 4
CONV_FILTER_TILE = 512


def _rows_sum(val, start: int, stop: int):
    """Rows [start, stop) of ``val`` (R, L) summed to (1, L): whole
    groups of 8 rows add vreg to vreg, a group the range cuts is masked."""
    width = val.shape[1]
    full0, full1 = -(-start // 8), stop // 8
    acc = None
    if full1 > full0:
        acc = val[8 * full0:8 * full1].reshape(
            full1 - full0, 8, width
        ).sum(axis=0)
    for g in range(start // 8, -(-stop // 8)):
        if full0 <= g < full1:
            continue
        rows = 8 * g + jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)
        cut = jnp.where(
            (rows >= start) & (rows < stop), val[8 * g:8 * g + 8], 0.0
        )
        acc = cut if acc is None else acc + cut
    return jnp.sum(acc, axis=0, keepdims=True)


def _conv_rectify_pool_kernel(
    segments, windows, max_val, alpha, precision,
    p_ref, w_ref, b_ref, out_ref,
):
    w = w_ref[:]
    bias = b_ref[:]
    for t in range(p_ref.shape[0]):
        x = jnp.dot(p_ref[t], w, preferred_element_type=jnp.float32,
                    precision=precision) - bias
        row = 0
        for part in (jnp.maximum(max_val, x - alpha),
                     jnp.maximum(max_val, -x - alpha)):
            sums = [_rows_sum(part, a, b) for a, b in segments]
            for window in windows:
                total = sums[window[0]]
                for s in window[1:]:
                    total = total + sums[s]
                out_ref[t, row:row + 1, :] = total
                row += 1


def conv_rectify_pool(
    patches: jnp.ndarray,
    w: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    segments: Sequence[Tuple[int, int]],
    windows: Sequence[Sequence[int]],
    max_val: float,
    alpha: float,
    precision=_HP,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Filter responses, symmetric rectifier and sum pooling in one pass.

    ``patches``: (n, R, K) — an image's patches as rows, in an order
    the caller chooses; ``w``: (K, F) filters as columns; ``bias``:
    (1, F), subtracted from every response. ``segments``: row ranges
    [start, stop) of ``patches`` (rows in no segment are padding and
    count nowhere); ``windows``: for each pooling window the segments
    whose sum it is. Returns (n, 2·len(windows), F): row i is window i
    of ``max(max_val, x − alpha)``, row len(windows) + i window i of
    ``max(max_val, −x − alpha)``, x = patches @ w − bias.

    R is a multiple of 8 and K of 128 (zero columns against zero rows
    of ``w`` count nothing). n and F are arbitrary: the last image
    tile and the last filter tile are partial blocks, whose rows and
    columns beyond the arrays are computed and dropped."""
    n, rows, k = patches.shape
    num_filters = w.shape[1]
    image_tile = min(CONV_IMAGE_TILE, n)
    filter_tile = min(CONV_FILTER_TILE, num_filters)
    out_rows = 2 * len(windows)
    kernel = partial(
        _conv_rectify_pool_kernel,
        tuple(tuple(s) for s in segments),
        tuple(tuple(win) for win in windows),
        float(max_val), float(alpha), precision,
    )
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, image_tile), pl.cdiv(num_filters, filter_tile)),
        in_specs=[
            pl.BlockSpec((image_tile, rows, k), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, filter_tile), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, filter_tile), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (image_tile, out_rows, filter_tile), lambda i, j: (i, 0, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n, out_rows, num_filters), jnp.float32
        ),
        interpret=auto_interpret(interpret),
    )(
        patches.astype(jnp.float32),
        w.astype(jnp.float32),
        bias.astype(jnp.float32),
    )


__all__ = [
    "auto_interpret", "sift_bin_sample", "plane_sandwich",
    "conv_rectify_pool",
]
