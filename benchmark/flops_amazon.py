"""Operations and bytes of the sparse logistic fit, from shapes and the
program's counters (beside flops.py, whose rule holds here: multiply-adds
as 2 operations against the chip's bf16 peak, the LEAST any path needs).

A fit on n rows of ``nnz`` non-zeros (the program's counter a fit) makes
``evaluations`` loss-and-gradient evaluations. Softmax logits matter only
through their differences, so one evaluation needs k − 1 products a
direction: X·ΔW (a multiply-add a non-zero and class) and Xᵀ(P − Y) (the
same), and reads each non-zero's column, row and value: 16 bytes a
non-zero (an index and a value a direction), plus W and the residuals.
"""

from __future__ import annotations


def sparse_grad(cfg: dict, n: int, evaluations: float = 0.0,
                nnz: float = 0.0) -> float:
    """The two products of every evaluation of one fit."""
    k = int(cfg["num_classes"])
    return evaluations * 2.0 * 2.0 * nnz * (k - 1)


def sparse_grad_bytes(cfg: dict, n: int, evaluations: float = 0.0,
                      nnz: float = 0.0, itemsize: int = 4) -> float:
    """16 bytes a non-zero an evaluation, and the (d, k) table and the
    (n, k − 1) logits and residuals once each."""
    k = int(cfg["num_classes"])
    d = int(cfg["num_features"])
    tables = itemsize * (d * k + 2.0 * n * (k - 1))
    return evaluations * (4.0 * itemsize * nnz + tables)


def amazon_fit(cfg: dict, n: int, evaluations: float = 0.0,
               nnz: float = 0.0) -> float:
    """One whole fit: its evaluations' products (the softmax and the
    optimizer's vector updates are O(n k) and O(d k) an evaluation, left
    out as the least)."""
    return sparse_grad(cfg, n, evaluations, nnz)
