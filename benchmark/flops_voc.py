"""Operations and bytes of VOCSIFTFisher fitted from images to model,
from shapes (see flops.py for the rules: the least any implementation
needs, multiply-adds as 2 operations against the bf16 peak, the six bf16
passes of a float32 product at ``highest`` not counted)."""

from __future__ import annotations

from benchmark.programs.voc import shape_counts


def frames(cfg: dict, h: int, w: int) -> list:
    """Per scale: (bin size, frames down, frames across) of the grid."""
    scales = int(cfg["sift_scales"])
    out = []
    for s in range(scales):
        bin_size = int(cfg["sift_bin"]) + 2 * s
        step = int(cfg["sift_step"]) + s * int(cfg["sift_scale_step"])
        bound = (1 + 2 * scales) - 3 * s
        out.append((bin_size, (h - 1 - bound - 3 * bin_size) // step + 1,
                    (w - 1 - bound - 3 * bin_size) // step + 1))
    return out


def descriptors(cfg: dict, h: int, w: int) -> int:
    return sum(ny * nx for _, ny, nx in frames(cfg, h, w))


def sift_image(cfg: dict, h: int, w: int) -> float:
    """Dense SIFT of one (h, w) image by flops.py's counting: smoothing
    on two axes, gradient and 8-way binning, and the triangular spatial
    binning in its separable form with the kernel's taps only."""
    total = 0.0
    for bin_size, ny, nx in frames(cfg, h, w):
        radius = -(-4 * bin_size // 6)  # ceil(4 sigma), sigma = bin / 6
        total += 2 * 2.0 * (2 * radius + 1) * h * w + 30.0 * h * w
        taps = 2 * bin_size - 1
        total += 8 * (2.0 * taps * (4 * ny) * w
                      + 2.0 * taps * (4 * ny) * (4 * nx))
    return total


def images(cfg: dict, rows: int) -> list:
    """[((h, w), how many)] of one chip's ``rows`` images."""
    return shape_counts(cfg["sizes"], rows)


def fv_stats(cfg: dict, rows: int) -> float:
    """The Fisher statistics of ``rows`` images: per descriptor the
    posteriors' two products (x and x² against k words) and the two
    statistics', 8 d k."""
    d, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    return sum(c * 8.0 * descriptors(cfg, h, w) * d * k
               for (h, w), c in images(cfg, rows))


def fv_stats_bytes(cfg: dict, rows: int) -> float:
    """Read the (d, m) descriptors once and the words' parameters, write
    the (2d + 1, k) statistics."""
    d, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    return sum(c * 4.0 * (d * descriptors(cfg, h, w) + 2 * (2 * d + 1) * k)
               for (h, w), c in images(cfg, rows))


def voc_fit(cfg: dict, rows: int, em_iterations: float = 1.0) -> float:
    """One whole fit on ``rows`` images: SIFT ONCE (the program computes
    it twice, under the PCA's sampler and under the projection, because
    it keeps only reduced descriptors; a pass that took both samples'
    columns and the reduced descriptors from one chunk would not, so the
    second is recomputation and does not count: PERF.md section 7 x),
    the PCA's covariance and the projection, the k-means++ start
    and ``em_iterations`` EM rounds over the GMM's sample, the Fisher
    vectors, and the block sweep (a Gram of n b (b + 1), a factorisation
    and the residual's two products a block)."""
    d, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    n_pca = (int(cfg["num_pca_samples"]) // rows) * rows
    n_gmm = (int(cfg["num_gmm_samples"]) // rows) * rows
    total = 2.0 * n_pca * 128 * 128  # covariance
    for (h, w), c in images(cfg, rows):
        m = descriptors(cfg, h, w)
        total += c * (sift_image(cfg, h, w) + 2.0 * d * 128 * m)
    total += (k - 1) * 2.0 * n_gmm * d + 2 * 2.0 * n_gmm * d * k  # start
    total += em_iterations * 8.0 * n_gmm * d * k
    total += fv_stats(cfg, rows)
    b, classes = int(cfg["block_size"]), int(cfg["num_classes"])
    blocks = -(-int(cfg["num_features"]) // b) * int(cfg["num_iter"])
    total += blocks * (rows * b * (b + 1.0) + b ** 3 / 3.0
                       + 2 * 2.0 * rows * b * classes)
    return total
