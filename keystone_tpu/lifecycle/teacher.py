"""Synthetic ground truth for labeled-load drills — numpy only.

``teacher_labels`` reproduces the demo pipeline's forward math
(``serving/demo_model.build_pipeline``: ``tanh(x @ W + b)`` per layer, the
identical ``default_rng`` draw order) without importing jax or the
serving stack, so ``serve-loadgen`` can synthesize labeled feedback
traffic against a live gateway from nothing but the model's shape
spec. ``head_seed`` redraws the FINAL layer from its own rng stream:
the served incumbent (head from ``seed``'s stream) is then a STALE
model of this teacher, which is exactly the drill setup — streaming
refit learns the teacher's head from feedback, and the candidate
must beat the incumbent on held-out teacher labels.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def teacher_weights(
    d: int, hidden: int, depth: int, seed: int = 0,
    head_seed: Optional[int] = None,
):
    """The demo chain's per-layer ``(W, b)`` list; with ``head_seed``
    the last layer is redrawn from ``default_rng(head_seed)``."""
    rng = np.random.default_rng(seed)
    dims = [d] + [hidden] * (depth - 1) + [d]
    layers = []
    for i in range(depth):
        w = rng.standard_normal((dims[i], dims[i + 1])).astype(
            np.float32
        ) / np.sqrt(dims[i])
        layers.append((w, np.zeros(dims[i + 1], np.float32)))
    if head_seed is not None:
        hrng = np.random.default_rng(head_seed)
        w = hrng.standard_normal((dims[depth - 1], dims[depth])).astype(
            np.float32
        ) / np.sqrt(dims[depth - 1])
        layers[-1] = (w, np.zeros(dims[depth], np.float32))
    return layers


def teacher_labels(
    X,
    d: int,
    hidden: int,
    depth: int,
    seed: int = 0,
    head_seed: Optional[int] = None,
) -> np.ndarray:
    """Ground-truth outputs for instances ``X`` under the (optionally
    head-redrawn) demo model — float32, same tanh chain as serving."""
    h = np.asarray(X, np.float32)
    if h.ndim != 2 or h.shape[1] != d:
        raise ValueError(f"want (n, {d}) instances, got {h.shape}")
    for w, b in teacher_weights(d, hidden, depth, seed, head_seed):
        h = np.tanh(h @ w + b).astype(np.float32)
    return h


__all__ = ["teacher_weights", "teacher_labels"]
