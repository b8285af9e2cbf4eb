"""Operations and bytes from shapes, for every roofline and mfu metric.

Each function counts the LEAST the algorithm needs under any
implementation, so that no later PR can read over 100%: work that an
implementation repeats (a Gram recomputed every epoch, the six bf16
passes of a float32 product at ``highest``) is not counted. Every count
is of multiply-adds as 2 operations against the chip's bf16 peak.
"""

from __future__ import annotations


def timit_gram(n: int, b: int) -> float:
    """One block's Gram X_bᵀX_b over n rows."""
    return 2.0 * n * b * b


def timit_gram_bytes(n: int, b: int, itemsize: int = 4) -> float:
    """Read the (n, b) block once, write the (b, b) Gram."""
    return float(itemsize) * (n * b + b * b)


def timit_fit(cfg: dict, n: int) -> float:
    """One whole TIMIT fit on n rows: the cosine features once, one Gram
    and one factorisation per block, and per epoch and block the
    right-hand side and the residual update in both directions."""
    d_in = int(cfg["dim"])
    b = int(cfg["num_cosine_features"])
    blocks = int(cfg["numCosines"])
    k = int(cfg["num_classes"])
    epochs = int(cfg["numEpochs"])
    features = 2.0 * n * d_in * b * blocks
    grams = blocks * timit_gram(n, b)
    factor = blocks * b ** 3 / 3.0
    sweeps = epochs * blocks * 2.0 * 2.0 * n * b * k
    return features + grams + factor + sweeps


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])


def timit_gram_step(cfg: dict, rows: int) -> float:
    """The Grams of one fit on a chip's ``rows``: one per block."""
    return int(cfg["numCosines"]) * timit_gram(rows, int(cfg["num_cosine_features"]))


def timit_gram_step_bytes(cfg: dict, rows: int) -> float:
    return int(cfg["numCosines"]) * timit_gram_bytes(
        rows, int(cfg["num_cosine_features"]))


def sift_frames(cfg: dict) -> list:
    """Per scale: (bin size, frames along one axis) of the dense grid."""
    size, scales = int(cfg["image_size"]), int(cfg["sift_scales"])
    out = []
    for s in range(scales):
        bin_size = int(cfg["sift_bin"]) + 2 * s
        step = int(cfg["sift_step"]) + s * int(cfg["sift_scale_step"])
        bound = (1 + 2 * scales) - 3 * s
        out.append((bin_size, (size - 1 - bound - 3 * bin_size) // step + 1))
    return out


def sift_bin_sample(cfg: dict, images: int) -> float:
    """The spatial binning of ``images`` images in its separable form,
    counting only the taps the triangular kernel has (2 bin - 1): per
    scale and orientation, the sampled rows over the image's width, then
    the sampled columns over the sampled rows."""
    size = int(cfg["image_size"])
    total = 0.0
    for bin_size, nf in sift_frames(cfg):
        taps, m = 2 * bin_size - 1, 4 * nf
        total += 8 * (2.0 * taps * m * size + 2.0 * taps * m * m)
    return images * total


def sift_bin_sample_bytes(cfg: dict, images: int) -> float:
    """Read magnitude and orientation, write the 8 binned planes."""
    size = int(cfg["image_size"])
    total = 0.0
    for _, nf in sift_frames(cfg):
        total += 4.0 * (2 * size * size + 8 * (4 * nf) ** 2)
    return images * total


def flagship_image(cfg: dict) -> float:
    """One image through the whole featurize-and-score path: smoothing,
    gradients and binning (separable, the kernels' taps only), LCS box
    filters, both PCA projections, the posteriors and the statistics of
    both Fisher vectors, and the linear model."""
    size = int(cfg["image_size"])
    dd, k = int(cfg["desc_dim"]), int(cfg["vocab_size"])
    px = size * size
    sift = sift_bin_sample(cfg, 1)
    for bin_size, _ in sift_frames(cfg):
        radius = -(-4 * bin_size // 6)  # ceil(4 sigma), sigma = bin / 6
        sift += 2 * 2.0 * (2 * radius + 1) * px  # smoothing, two axes
        sift += 30.0 * px  # gradient, magnitude, angle, 8-way binning
    s = int(cfg["lcs_patch"])
    lcs = 6 * 2 * 2.0 * s * px  # box filters of 3 channels and squares
    m_sift = sum(nf * nf for _, nf in sift_frames(cfg))
    border, stride = int(cfg["lcs_border"]), int(cfg["lcs_stride"])
    m_lcs = len(range(border, size - border, stride)) ** 2
    fisher = 0.0
    for d_in, m in ((128, m_sift), (96, m_lcs)):
        fisher += 2.0 * dd * d_in * m  # PCA
        fisher += 2 * 2.0 * dd * k * m  # posteriors: x and x*x against k
        fisher += 2 * 2.0 * dd * k * m  # first and second statistics
    model = 2.0 * (2 * 2 * dd * k) * int(cfg["num_classes"])
    return sift + lcs + fisher + model


def flagship_step(cfg: dict, images: int) -> float:
    return images * flagship_image(cfg)
