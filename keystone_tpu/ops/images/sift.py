"""Dense multi-scale SIFT.

Reference: nodes/images/external/SIFTExtractor.scala:16 +
src/main/cpp/VLFeat.cxx:36-200 (getMultiScaleDSIFTs_f driving vlfeat
0.9.20's vl_dsift). The multi-scale driver here matches VLFeat.cxx
exactly: per scale s, bin size = bin + 2s, Gaussian pre-smoothing with
sigma = binSize/magnif (magnif = 6), sampling bounds offset
(1 + 2·numScales) − 3s to the image edge, step = step + s·scaleStep,
contrast-threshold 0.005 zeroing of low-energy descriptors, descriptors
scaled x512 and clamped to 255 (the MATLAB uint8 convention,
VLFeat.cxx:230-260).

The per-scale descriptor follows vl_dsift's dense formulation: 4x4
spatial bins x 8 orientations; gradient magnitude is binned bilinearly
over orientation; spatial binning is the triangular (bilinear)
convolution vl_imconvcoltri implements; bins are modulated by the
Gaussian window factor (windowSize = 1.5, flat-window approximation
evaluates it per bin center); each descriptor is L2-normalized, clamped
at 0.2, renormalized (Lowe's normalization).

NOTE: the reference's golden fixture (feats128.csv, ±1-of-99.5% vs MATLAB
vl_phow) is not present in its repo, and vlfeat sources are not available
in this environment, so bit-level parity against vlfeat cannot be
asserted here; the algorithm is validated against an independent numpy
translation of the same spec (tests/ops/test_sift_fv.py).

TPU mapping: the whole spatial-binning stage (triangular convolution +
bin-center sampling + Gaussian window factors) folds into two small
per-scale SAMPLING MATRICES applied as MXU GEMMs. The stage is linear
in the orientation planes and separable per axis, so
``A[y, f·4+j] = tri(y − (bound + f·step + j·bin)) · wf[j]`` expresses
tri-conv→sample→window exactly (the C=1 depthwise convs it replaced
ran on the VPU and the slicing materialized awkwardly-tiled
intermediates). The binning+GEMM hot loop itself runs as the
``pallas_kernels.sift_bin_sample`` kernel: the trilinear orientation
scatter and both sampling-matrix contractions fuse in VMEM, so the
(8, H, W) plane stack never hits HBM (Mosaic-compiled on a TPU; the
CPU backend interprets the same kernel). Neither formulation has a
number from the current machine (ROADMAP S1). Static shapes per
(W, H, scale).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.ops.images.pallas_kernels import sift_bin_sample
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Transformer

NUM_ORIENTATIONS = 8
NUM_SPATIAL_BINS = 4
DESCRIPTOR_DIMS = 128
MAGNIF = 6.0
CONTRAST_THRESHOLD = 0.005
WINDOW_SIZE = 1.5


def _gaussian_kernel(sigma: float) -> np.ndarray:
    """vl_imsmooth-style truncated Gaussian (radius ceil(4 sigma))."""
    if sigma < 1e-8:
        return np.ones(1, np.float32)
    r = int(np.ceil(4.0 * sigma))
    xs = np.arange(-r, r + 1)
    k = np.exp(-(xs**2) / (2.0 * sigma * sigma))
    return (k / k.sum()).astype(np.float32)


def _sep_conv2d(planes: jnp.ndarray, k: np.ndarray) -> jnp.ndarray:
    """Separable same-size conv of (P, H, W) planes with a 1-D kernel,
    borders replicated (vl_imsmooth's continuity padding). Only the
    Gaussian pre-smooth comes through here — the triangular spatial
    binning is folded into the sampling-matrix GEMMs
    (_sampling_matrix)."""
    kj = jnp.asarray(k)
    pad = (len(k) - 1) // 2

    def conv1d(x, axis):
        moved = jnp.moveaxis(x, axis, -1)
        shape = moved.shape
        flat = moved.reshape(-1, 1, shape[-1])
        if pad > 0:
            flat = jnp.pad(
                flat, ((0, 0), (0, 0), (pad, pad)), mode="edge"
            )
        out = jax.lax.conv_general_dilated(
            flat, kj[None, None, :], (1,), [(0, 0)],
            dimension_numbers=("NCH", "OIH", "NCH"),
        )
        return jnp.moveaxis(
            out.reshape(shape[:-1] + (out.shape[-1],)), -1, axis
        )

    return conv1d(conv1d(planes, 1), 2)


def _window_factors(bin_size: int) -> np.ndarray:
    """Per-bin Gaussian window factor at bin centers (flat-window
    approximation): exp(−½ (δ/σ_win)²), σ_win = windowSize·binSize, δ =
    bin-center offset from the descriptor center."""
    centers = (
        np.arange(NUM_SPATIAL_BINS) - (NUM_SPATIAL_BINS - 1) / 2.0
    ) * bin_size
    sigma = WINDOW_SIZE * bin_size
    return np.exp(-0.5 * (centers / sigma) ** 2).astype(np.float32)


def _sampling_matrix(
    n: int, nf: int, bin_size: int, step: int, bound: int
) -> np.ndarray:
    """(n, nf·4) one-axis spatial-binning operator: column f·4+j holds
    the triangular kernel tri(d) = max(0, (bin−|d|)/bin) centered at
    bound + f·step + j·bin (zero outside the image — vl_imconvcoltri's
    zero padding), pre-scaled by the Gaussian window factor wf[j].
    Applying it on each axis reproduces triangular conv → bin-center
    sample → window EXACTLY (the stage is linear and separable), as two
    MXU GEMMs instead of VPU-bound C=1 convs plus slicing. Built per
    trace — jit's per-static-shape caching makes memoization redundant,
    and the build is nf·4 tiny numpy rows."""
    wf = _window_factors(bin_size)
    m = np.zeros((n, nf * NUM_SPATIAL_BINS), np.float32)
    ys = np.arange(n)
    for f in range(nf):
        for j in range(NUM_SPATIAL_BINS):
            c = bound + f * step + j * bin_size
            tri = np.maximum(0.0, (bin_size - np.abs(ys - c)) / bin_size)
            m[:, f * NUM_SPATIAL_BINS + j] = tri * wf[j]
    return m


@partial(jax.jit, static_argnames=("bin_size", "step", "bound_min"))
def _dsift_one_scale(img, *, bin_size: int, step: int, bound_min: int):
    """Dense SIFT at one scale over a pre-smoothed (H, W) image.

    Returns (num_frames, 128) raw descriptors (normalized + clamped) and
    (num_frames,) pre-normalization norms. Frame grid: top-left corners
    at bound_min + f·step along both axes, descriptor extent
    4·binSize."""
    H, W = img.shape
    with jax.named_scope("sift.gradient"):
        gy, gx = jnp.gradient(img)
        mag = jnp.sqrt(gx * gx + gy * gy)
        ang = jnp.arctan2(gy, gx) % (2.0 * jnp.pi)
        t = ang / (2.0 * jnp.pi) * NUM_ORIENTATIONS

    extent = (NUM_SPATIAL_BINS - 1) * bin_size
    nfy = max((H - 1 - bound_min - extent) // step + 1, 0)
    nfx = max((W - 1 - bound_min - extent) // step + 1, 0)
    if nfy == 0 or nfx == 0:
        return (
            jnp.zeros((0, DESCRIPTOR_DIMS), jnp.float32),
            jnp.zeros((0,), jnp.float32),
        )
    # the whole tri-conv → bin-sample → window stage as two GEMMs (see
    # _sampling_matrix), fused with the trilinear orientation binning
    # in one Pallas kernel — each orientation plane is built and
    # contracted in VMEM, never written to HBM
    Ay = _sampling_matrix(H, nfy, bin_size, step, bound_min)
    Ax = jnp.asarray(_sampling_matrix(W, nfx, bin_size, step, bound_min))
    # the kernel call carries no named_scope: XLA names the custom call
    # after the innermost scope, and benchmark/metrics/
    # sift_roofline_pct.score.json finds it as ``..dsift_one_scale__.N``
    g = sift_bin_sample(mag, t, jnp.asarray(Ay.T.copy()), Ax)
    with jax.named_scope("sift.normalize"):
        g = g.reshape(
            NUM_ORIENTATIONS, nfy, NUM_SPATIAL_BINS, nfx, NUM_SPATIAL_BINS
        )
        g = jnp.transpose(g, (1, 3, 2, 4, 0))  # (nfy, nfx, j, i, t)
        raw = g.reshape(-1, DESCRIPTOR_DIMS)
        norms = jnp.linalg.norm(raw, axis=1)
        desc = raw / jnp.maximum(norms, 1e-12)[:, None]
        desc = jnp.minimum(desc, 0.2)
        desc = desc / jnp.maximum(
            jnp.linalg.norm(desc, axis=1), 1e-12
        )[:, None]
    return desc, norms


@dataclasses.dataclass(eq=False)
class SIFTExtractor(Transformer):
    """Image -> (128, numDescriptors) short-valued descriptor matrix
    (reference: SIFTExtractor.scala — the columns are descriptors)."""

    step: int = 3
    bin: int = 4
    num_scales: int = 4
    scale_step: int = 1  # reference default (SIFTExtractor.scala:16)
    vmap_batch = False  # ragged across shapes
    bucket_vmap = True  # but vmappable within a shape bucket

    def apply(self, img):
        x = jnp.asarray(img, jnp.float32)
        if x.ndim == 3:
            x = x[:, :, 0]
        H, W = x.shape
        descs: List[jnp.ndarray] = []
        for scale in range(self.num_scales):
            bin_size = self.bin + 2 * scale
            sigma = bin_size / MAGNIF
            k = _gaussian_kernel(sigma)
            with jax.named_scope("sift.smooth"):
                sm = _sep_conv2d(x[None], k)[0]
            bound = (1 + 2 * self.num_scales) - 3 * scale
            desc, norms = _dsift_one_scale(
                sm,
                bin_size=bin_size,
                step=self.step + scale * self.scale_step,
                bound_min=bound,
            )
            # contrast-threshold zeroing (VLFeat.cxx:141-175)
            desc = jnp.where(
                (norms >= CONTRAST_THRESHOLD)[:, None], desc, 0.0
            )
            descs.append(desc)
        all_desc = jnp.concatenate(descs, axis=0)
        # x512, clamp 255, to the uint8-style convention (VLFeat.cxx glue)
        quantized = jnp.minimum(
            jnp.floor(all_desc * 512.0), 255.0
        )
        return quantized.T  # (128, numDescriptors)

    def rowwise(self):
        return _SiftRows(
            self.step, self.bin, self.num_scales, self.scale_step), ()

    @property
    def descriptor_dims(self) -> int:
        return DESCRIPTOR_DIMS


@dataclasses.dataclass(frozen=True)
class _SiftRows:
    """The SIFTExtractor's rows-in, rows-out function over images of one
    shape: extractors of equal settings share the compiled programs."""

    step: int
    bin: int
    num_scales: int
    scale_step: int
    groups_only = True

    def __call__(self, arrays, imgs):
        del arrays
        return jax.vmap(SIFTExtractor(
            self.step, self.bin, self.num_scales, self.scale_step).apply
        )(imgs)

    # an image's descriptors are live twice, descriptor-major as the
    # scales leave them and transposed as the node hands them over
    held = __call__
