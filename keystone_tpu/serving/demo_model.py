"""The served demo model: a seeded chain of ``tanh(x @ W + b)`` nodes.

``serve-gateway``, ``serve-loadgen --self-gateway``, ``serve-capacity-plan``
and a zoo spec's ``build`` all serve this one model
(serving a saved ``FittedPipeline`` from the command line is ROADMAP R6),
and the serving plane's tests build their engines from it. The weights
are a function of ``(d, hidden, depth, seed)`` alone, so two processes
given the same arguments hold the same parameters, the same
``featurize_token`` and the same compile-cache keys.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np

from keystone_tpu.workflow.api import Transformer


@dataclasses.dataclass(eq=False)
class _Affine(Transformer):
    """Per-example tanh(x @ W + b) — enough real work per node that the
    staged program isn't trivially constant-folded."""

    W: Any
    b: Any

    def apply(self, x):
        return jnp.tanh(x @ self.W + self.b)


def build_pipeline(
    d: int = 256, hidden: int = 512, depth: int = 4, seed: int = 0
):
    """An estimator-free array-mode chain -> FittedPipeline (depth
    matmul nodes).
    ``seed`` varies the weights — the zoo spec loader uses it so two
    same-shaped models carry distinct params (and therefore distinct
    tokens and compiled programs)."""
    rng = np.random.default_rng(seed)
    dims = [d] + [hidden] * (depth - 1) + [d]
    pipe = None
    for i in range(depth):
        w = jnp.asarray(
            rng.standard_normal((dims[i], dims[i + 1])).astype(np.float32)
            / np.sqrt(dims[i])
        )
        b = jnp.asarray(np.zeros(dims[i + 1], np.float32))
        node = _Affine(w, b)
        pipe = node.to_pipeline() if pipe is None else pipe.and_then(node)
    return pipe.to_pipeline().fit()


def affine_head(W, b):
    """One ``tanh(x @ W + b)`` node as a standalone FittedPipeline —
    the refittable HEAD the online-lifecycle loop re-solves.
    ``base.and_then(affine_head(W, b))`` composes it back onto a
    feature base; with the weights drawn by ``build_split_pipeline``
    the composition is the same graph ``build_pipeline`` builds."""
    W = jnp.asarray(np.asarray(W, np.float32))
    b = jnp.asarray(np.asarray(b, np.float32))
    return _Affine(W, b).to_pipeline().to_pipeline().fit()


def build_split_pipeline(
    d: int = 256, hidden: int = 512, depth: int = 4, seed: int = 0
):
    """``build_pipeline`` split at the last layer: returns
    ``(base, W, b)`` where ``base`` is the first ``depth - 1`` layers
    (the frozen featurizer the refit accumulator reads activations
    from) and ``(W, b)`` is the final layer's weights.
    ``base.and_then(affine_head(W, b))`` serves OUTPUTS BITWISE EQUAL
    to ``build_pipeline(d, hidden, depth, seed)`` — the rng stream is
    drawn in the identical order — so a gateway can boot on the split
    form and the lifecycle loop can re-solve just the head."""
    if depth < 2:
        raise ValueError(f"split needs depth >= 2, got {depth}")
    rng = np.random.default_rng(seed)
    dims = [d] + [hidden] * (depth - 1) + [d]
    pipe = None
    for i in range(depth - 1):
        w = jnp.asarray(
            rng.standard_normal((dims[i], dims[i + 1])).astype(np.float32)
            / np.sqrt(dims[i])
        )
        b = jnp.asarray(np.zeros(dims[i + 1], np.float32))
        node = _Affine(w, b)
        pipe = node.to_pipeline() if pipe is None else pipe.and_then(node)
    head_w = jnp.asarray(
        rng.standard_normal((dims[depth - 1], dims[depth])).astype(
            np.float32
        )
        / np.sqrt(dims[depth - 1])
    )
    head_b = jnp.asarray(np.zeros(dims[depth], np.float32))
    return pipe.to_pipeline().fit(), head_w, head_b


__all__ = ["affine_head", "build_pipeline", "build_split_pipeline"]
