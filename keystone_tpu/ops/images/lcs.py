"""Local Color Statistics (LCS) extractor.

Reference: nodes/images/LCSExtractor.scala:25 — per grid keypoint, the
means and standard deviations of each RGB channel over a 4x4 neighborhood
of sub-patches (96-dim descriptors); means/stds come from a centered box
filter (ImageUtils.conv2D zero-pads floor((L-1)/2) low / rest high, so an
even-length box is right-biased exactly as the reference's).

TPU mapping: the box filter is linear and separable, and the keypoint/
neighborhood positions are affine in (key, neighbor) — so box-mean →
sample folds into one per-axis SAMPLING MATRIX applied as MXU GEMMs
(same reformulation as SIFT's spatial binning, sift.py
``_sampling_matrix``), once on the image for means and once on its
square for the variances. No convs, no gathers. The GEMM pair runs as
the ``pallas_kernels.plane_sandwich`` kernel — each channel plane
(image and image² stacked) stays VMEM-resident between its two dots
(Mosaic-compiled on a TPU; the CPU backend interprets the same kernel).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.ops.images.pallas_kernels import plane_sandwich
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Transformer


def _box_filter_same(img: jnp.ndarray, size: int) -> jnp.ndarray:
    """(H, W, C) -> same-size box mean with the reference's asymmetric
    zero padding (ImageUtils.conv2D:226-238)."""
    pad_low = (size - 1) // 2
    pad_high = size - 1 - pad_low
    k = jnp.full((size,), 1.0 / size, jnp.float32)

    def conv_axis(x, axis):
        moved = jnp.moveaxis(x, axis, -1)
        shape = moved.shape
        flat = moved.reshape(-1, 1, shape[-1])
        out = jax.lax.conv_general_dilated(
            flat, k[None, None, :], (1,), [(pad_low, pad_high)],
            dimension_numbers=("NCH", "OIH", "NCH"),
            precision=jax.lax.Precision.HIGHEST,  # validated at 1e-4 vs
            # the naive translation; TPU DEFAULT lands at ~1e-3
        )
        return jnp.moveaxis(out.reshape(shape), -1, axis)

    return conv_axis(conv_axis(img, 0), 1)


def _lcs_sampling_matrix(
    n: int, keys: np.ndarray, offs: np.ndarray, s: int
) -> np.ndarray:
    """(n, n_keys·nb) one-axis operator: column k·nb + j holds the 1/s
    box window whose output position is keys[k] + offs[j] under the
    reference's asymmetric zero padding (window start = pos −
    floor((s−1)/2); out-of-image taps drop, matching conv2D's zero
    pad). Box-filter → sample is linear and separable, so applying this
    per axis reproduces it exactly as MXU GEMMs."""
    pad_low = (s - 1) // 2
    nb = len(offs)
    m = np.zeros((n, len(keys) * nb), np.float32)
    for k, x0 in enumerate(keys):
        for j, o in enumerate(offs):
            lo = x0 + o - pad_low
            for t in range(s):
                p = lo + t
                if 0 <= p < n:
                    m[p, k * nb + j] += 1.0 / s
    return m


@dataclasses.dataclass(eq=False)
class LCSExtractor(Transformer):
    """Image (X, Y, C) -> (numLCSValues, numKeypoints) descriptor matrix,
    column xKey·numPoolsY + yKey, row order: for each channel, for each
    (nx, ny) neighbor: [mean, std] interleaved (LCSExtractor.scala:96-127).
    """

    stride: int
    stride_start: int
    sub_patch_size: int
    vmap_batch = False  # ragged across shapes
    bucket_vmap = True  # but vmappable within a shape bucket

    def apply(self, img):
        return self._extract(jnp.asarray(img, jnp.float32))

    @partial(jax.jit, static_argnums=(0,))
    def _extract(self, img):
        s = self.sub_patch_size
        X, Y, C = img.shape
        xs = np.arange(self.stride_start, X - self.stride_start, self.stride)
        ys = np.arange(self.stride_start, Y - self.stride_start, self.stride)
        # neighborhood offsets: -2s + s/2 - 1 .. s + s/2 - 1 step s
        start = -2 * s + s // 2 - 1
        end = s + s // 2 - 1
        offs = np.arange(start, end + 1, s)

        Ax = _lcs_sampling_matrix(X, xs, offs, s)
        Ay = jnp.asarray(_lcs_sampling_matrix(Y, ys, offs, s))
        # image and its square share the GEMM chain (stacked channel
        # planes through the Pallas sandwich kernel; HIGHEST-precision
        # dots in-kernel — validated at 1e-4 vs the naive translation,
        # TPU DEFAULT lands at ~1e-3)
        with jax.named_scope("lcs.box_sample"):
            z = jnp.concatenate([img, img * img], axis=-1)
            out = plane_sandwich(
                jnp.transpose(z, (2, 0, 1)), jnp.asarray(Ax.T.copy()), Ay
            )
        with jax.named_scope("lcs.moments"):
            both = jnp.transpose(out, (1, 2, 0))  # (nxk·nb, nyk·nb, 2C)
            m, sq = both[..., :C], both[..., C:]
            sd = jnp.sqrt(jnp.maximum(sq - m * m, 0.0))

        nxk, nyk, nb = len(xs), len(ys), len(offs)

        # target layout rows: c, nx, ny -> interleaved mean/std;
        # columns: xKey * numPoolsY + yKey
        def arrange(z):
            z = z.reshape(nxk, nb, nyk, nb, C)
            return jnp.transpose(z, (4, 1, 3, 0, 2))  # (C, nbx, nby, xk, yk)

        with jax.named_scope("lcs.arrange"):
            inter = jnp.stack([arrange(m), arrange(sd)], axis=3)
            return inter.reshape(-1, nxk * nyk)
