"""Operations and bytes of the mixture-weighted block fit, from shapes
(beside flops.py, whose rule holds here: multiply-adds as 2 operations
against the chip's bf16 peak, and for the whole fit the LEAST under any
of the estimator's own paths, so that the share cannot pass 100%).

n rows, b features in a block, C classes, ``iterations`` the CG
iterations a fit took (the program's counter; the direct path takes none).
"""

from __future__ import annotations


def sizes(cfg: dict) -> tuple:
    """(b, blocks, C, passes) of a configuration."""
    d = int(cfg["num_features"])
    b = min(int(cfg["block_size"]), d)
    return b, -(-d // b), int(cfg["num_classes"]), int(cfg["num_iter"])


def wls_gram(n: int, b: int) -> float:
    """One block's Gram X_bᵀX_b, which every path builds."""
    return 2.0 * n * b * b


def wls_moments(n: int, b: int, c: int) -> float:
    """A block step's moments: X_bᵀR against the dense residual, and the
    classes' sums and own-residual sums (one add, one multiply-add a
    row and feature)."""
    return 2.0 * n * b * c + n * b + 2.0 * n * b


def wls_direct(n: int, b: int, c: int) -> float:
    """The direct path's solve: the classes' covariances (every row in
    one class: n b² multiply-adds) and C Cholesky factorisations."""
    return 2.0 * n * b * b + c * b ** 3 / 3.0


def wls_matvec(n: int, b: int, c: int) -> float:
    """The two data-sized products of one CG iteration as the one-hot
    formulation has them: X_b v for every class, and X_bᵀ(P ⊙ z)."""
    return 2.0 * 2.0 * n * b * c


def wls_matrix_free(n: int, b: int, c: int, iterations: float) -> float:
    """The matrix-free path's solve: a CG iteration's two data-sized
    products, the population covariance's and the preconditioner's
    (C, b) x (b, b) products."""
    return iterations * (wls_matvec(n, b, c) + 2 * 2.0 * c * b * b)


def wls_fit(cfg: dict, n: int, iterations: float = 0.0) -> float:
    """One whole weighted fit on n rows. Per block step the Gram, the
    moments and the smaller of the direct and the matrix-free solve (the
    direct one where no iteration count is given); the residual update
    (2 n b C) for every block step but the last, whose residual nothing
    reads."""
    b, blocks, c, passes = sizes(cfg)
    steps = blocks * passes
    solve = wls_direct(n, b, c)
    if iterations:
        solve = min(solve, wls_matrix_free(n, b, c, iterations))
    step = wls_gram(n, b) + wls_moments(n, b, c) + solve
    return steps * step + (steps - 1) * 2.0 * n * b * c


def wls_matvec_step(cfg: dict, n: int, iterations: float = 0.0) -> float:
    """The data-sized products of all of a fit's CG iterations."""
    b, blocks, c, passes = sizes(cfg)
    return blocks * passes * iterations * wls_matvec(n, b, c)


def wls_matvec_step_bytes(cfg: dict, n: int, iterations: float = 0.0,
                          itemsize: int = 4) -> float:
    """Two reads of the block's features a CG iteration."""
    b, blocks, _, passes = sizes(cfg)
    return blocks * passes * iterations * 2.0 * itemsize * n * b
