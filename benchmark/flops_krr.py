"""Operations and bytes of the RandomPatchCifarAugmentedKernel fit, from
shapes (beside flops.py, whose rule holds here: multiply-adds as 2
operations against the chip's bf16 peak, and the LEAST any path needs, so
that no share can pass 100%: the three bf16 passes of the kernel's cross
term and the six of a product at ``highest`` are not counted, nor the
exponentials, nor a column block generated again in a later epoch, which
a path that caches the kernel matrix never does).

n rows (crops), res x res positions a crop, P values a patch, F filters,
d = 2 x 2 x 2F features, blocks of at most b rows, k classes. The kernel
matrix is counted whole, n x n: K is symmetric, but a column-block sweep
could use that only by keeping the blocks it has made, n^2 / 2 entries.
"""

from __future__ import annotations


def sizes(cfg: dict) -> tuple:
    """(positions a crop, P, F, d, k)."""
    patch = int(cfg["patch_size"])
    res = (int(cfg["augment_patch_size"]) - patch) \
        // int(cfg["patch_steps"]) + 1
    return (res * res, patch * patch * int(cfg["image"][2]),
            int(cfg["num_filters"]), int(cfg["num_features"]),
            int(cfg["num_classes"]))


def widths(cfg: dict, n: int) -> list:
    """The row blocks' widths."""
    b = int(cfg["block_size"])
    return [min(b, n - s) for s in range(0, n, b)]


def conv(cfg: dict, n: int) -> float:
    """Every patch of n crops against every filter."""
    positions, p, f, _, _ = sizes(cfg)
    return 2.0 * n * positions * p * f


def kernel_block(cfg: dict, n: int, w: int) -> float:
    """One column block's cross term X X_B': (n, d) by (d, w)."""
    return 2.0 * n * w * sizes(cfg)[3]


def kernel_block_bytes(cfg: dict, n: int, w: int, itemsize: int = 4) -> float:
    """Read the rows and the block's rows, write the (n, w) block."""
    d = sizes(cfg)[3]
    return float(itemsize) * (n * d + w * d + n * w)


def kernel(cfg: dict, n: int) -> float:
    """Every column block once: 2 n^2 d."""
    return sum(kernel_block(cfg, n, w) for w in widths(cfg, n))


def kernel_bytes(cfg: dict, n: int) -> float:
    return sum(kernel_block_bytes(cfg, n, w) for w in widths(cfg, n))


def sweeps(cfg: dict, n: int) -> float:
    """Per epoch and block: K_B' W over all rows, K_BB' W_B, and two
    triangular solves of k right-hand sides; per block once, a Cholesky
    factorisation (a path that keeps the factors makes each once)."""
    k = sizes(cfg)[4]
    per_epoch = sum(2.0 * n * w * k + 2.0 * w * w * k + 2.0 * w * w * k
                    for w in widths(cfg, n))
    factor = sum(w ** 3 / 3.0 for w in widths(cfg, n))
    return int(cfg["num_epochs"]) * per_epoch + factor


def krr_fit(cfg: dict, n: int) -> float:
    """One whole fit on n rows: the convolution, the kernel matrix and
    the sweeps. Crops, flips, the filters' ZCA (a 108 x 108 system), the
    rectifier, the pooler and the scaler are copies, additions and
    comparisons, a thousandth of this."""
    return conv(cfg, n) + kernel(cfg, n) + sweeps(cfg, n)


def kernel_step(cfg: dict, rows: int) -> float:
    """The column blocks of one fit on a chip's ``rows``."""
    return kernel(cfg, rows)


def kernel_step_bytes(cfg: dict, rows: int) -> float:
    return kernel_bytes(cfg, rows)
