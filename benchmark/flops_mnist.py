"""Operations and bytes of the MnistRandomFFT fit, from shapes (see
flops.py for the rules: the least any implementation needs,
multiply-adds as 2 operations against the bf16 peak, the six bf16 passes
of a float32 product at ``highest`` not counted).

n rows of d pixels, F branches, each a real FFT of ``pad`` points of
which pad / 2 real parts are kept: D = F pad / 2 features in blocks of
b, k classes.
"""

from __future__ import annotations

import math


def sizes(cfg: dict) -> tuple:
    """(d, pad, F, D, the blocks' widths, k)."""
    d, pad = int(cfg["image_pixels"]), int(cfg["fft_pad"])
    f, dd = int(cfg["num_ffts"]), int(cfg["num_features"])
    b = int(cfg["block_size"])
    widths = [min(b, dd - s) for s in range(0, dd, b)]
    return d, pad, f, dd, widths, int(cfg["num_classes"])


def fft_bank(cfg: dict, rows: int) -> float:
    """The featurizer over ``rows``: a real FFT's 2.5 N log2 N a branch
    and row, whatever computes it (a DFT as one product does ~40 times
    the work; the sign products and the rectifier are comparisons and
    multiplies by ±1, not counted)."""
    _, pad, f, _, _, _ = sizes(cfg)
    return rows * f * 2.5 * pad * math.log2(pad)


def fft_bank_bytes(cfg: dict, rows: int, itemsize: int = 4) -> float:
    """Read the (rows, d) images, write the (rows, D) features."""
    d, _, _, dd, _, _ = sizes(cfg)
    return float(itemsize) * rows * (d + dd)


def solver(cfg: dict, rows: int) -> float:
    """One Gauss-Seidel sweep: per block the Gram at its least,
    n b (b + 1) (its upper triangle with the diagonal), the right-hand
    side and a Cholesky factorisation, and the residual update for every
    block but the last, whose residual nothing reads."""
    _, _, _, _, widths, k = sizes(cfg)
    sweeps = int(cfg["num_iter"])
    total = 0.0
    for w in widths:
        total += rows * w * (w + 1.0) + 2.0 * rows * w * k + w ** 3 / 3.0
        total += 2.0 * rows * w * k
    return sweeps * total - 2.0 * rows * widths[-1] * k


def mnist_fit(cfg: dict, rows: int) -> float:
    """One whole fit on ``rows``: the FFT bank and the block sweep."""
    return fft_bank(cfg, rows) + solver(cfg, rows)
