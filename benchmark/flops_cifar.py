"""Operations and bytes of the RandomPatchCifar fit, from shapes (beside
flops.py, whose rule holds here: multiply-adds as 2 operations against
the chip's bf16 peak, and the LEAST any path needs, so that no share can
pass 100%: the six bf16 passes of a float32 product at ``highest`` are
not counted, nor the maps a path writes and reads between its nodes).

n rows, res x res positions an image, P values a patch, F filters,
D = 2 x 2 x 2F features in blocks of at most b, k classes.
"""

from __future__ import annotations


def sizes(cfg: dict) -> tuple:
    """(positions an image, P, F, D, the blocks' widths, k)."""
    side, _, channels = cfg["image"]
    patch = int(cfg["patch_size"])
    res = (int(side) - patch) // int(cfg["patch_steps"]) + 1
    d, b = int(cfg["num_features"]), int(cfg["block_size"])
    widths = [min(b, d - s) for s in range(0, d, b)]
    return (res * res, patch * patch * int(channels),
            int(cfg["num_filters"]), d, widths, int(cfg["num_classes"]))


def conv(cfg: dict, n: int) -> float:
    """Every patch of n images against every filter."""
    positions, p, f, _, _, _ = sizes(cfg)
    return 2.0 * n * positions * p * f


def conv_bytes(cfg: dict, n: int, itemsize: int = 4) -> float:
    """Read the images and the filters, write the pooled features: a
    path that fuses the rectifier and the pooler into the convolution
    never writes a map."""
    side, _, channels = cfg["image"]
    _, p, f, d, _, _ = sizes(cfg)
    return float(itemsize) * (n * side * side * channels + f * p + n * d)


def solver(cfg: dict, n: int) -> float:
    """One Gauss-Seidel sweep: per block the Gram, the right-hand side
    and a Cholesky factorisation, and the residual update for every
    block but the last, whose residual nothing reads."""
    _, _, _, _, widths, k = sizes(cfg)
    sweeps = int(cfg["num_iter"])
    total = 0.0
    for w in widths:
        total += 2.0 * n * w * w + 2.0 * n * w * k + w ** 3 / 3.0
        total += 2.0 * n * w * k
    return sweeps * total - 2.0 * n * widths[-1] * k


def cifar_fit(cfg: dict, n: int) -> float:
    """One whole fit on n rows: the convolution and the solver. The
    filters' ZCA (a 108 x 108 system), the rectifier, the pooler and the
    scaler are additions and comparisons, a thousandth of this."""
    return conv(cfg, n) + solver(cfg, n)


def conv_step(cfg: dict, rows: int) -> float:
    """The convolutions of one fit on a chip's ``rows``."""
    return conv(cfg, rows)


def conv_step_bytes(cfg: dict, rows: int) -> float:
    return conv_bytes(cfg, rows)
