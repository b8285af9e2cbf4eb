"""Streaming refit: labeled feedback → incremental normal equations →
a re-solved head.

The served demo model ends in a ``tanh(x @ W + b)`` head over a frozen
feature base (``serving/demo_model.build_split_pipeline``). Because the
normal-equations state is ADDITIVE — the same property that makes the
ELL one-pass accumulator in ``ops/learning/sparse_ell.py``
chunk-size-independent — "refit" is never a full refit: each labeled
chunk folds into ``(G, AY, n)`` once and a candidate head is one
regularized PSD solve over the running state (the identical
``_psd_solve_device`` kernel the ELL solver jits).

Math: serving outputs are ``y = tanh(z)`` with ``z = h @ W + b`` over
base features ``h``, so labels are mapped to pre-activation targets
``z = arctanh(clip(y))`` and the head is the ridge solution of the
AUGMENTED system ``[h, 1] @ W_aug = z`` — the ones column carries the
bias, and a 0/1 validity mask zeroes padded rows so every chunk runs
through ONE fixed-shape jitted update (one XLA compile total).

Held-out labels: every ``holdout_every``-th feedback row is diverted
to a bounded holdout buffer and NEVER accumulated — the accuracy gate
compares candidate vs incumbent on data neither was solved from. The
``lifecycle.refit.poison`` chaos point corrupts an accumulated chunk's
targets (the holdout stays clean), which is exactly how the rollback
drill proves the accuracy gate fires.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.loadgen import faults
# the ELL accumulator's solve kernel (sparse_ell jits the same fn):
# refit state is (G, AY, n) exactly like its one-pass scan, so the
# candidate head comes out of the identical factor-and-refine solve
from keystone_tpu.ops.learning.block_ls import _psd_solve_device

_jit_psd_solve = jax.jit(_psd_solve_device)

# labels are tanh outputs in (-1, 1); clip before arctanh so a label
# AT the rail maps to a large-but-finite pre-activation target
_CLIP = 1.0 - 1e-5


@jax.jit
def _accum_update(G, AY, H, Z, mask):
    Ha = jnp.concatenate([H * mask[:, None], mask[:, None]], axis=1)
    return G + Ha.T @ Ha, AY + Ha.T @ (Z * mask[:, None])


class RefitAccumulator:
    """Incremental ``(G, AY, n)`` over a frozen feature base, plus the
    clean holdout buffer the accuracy gate reads."""

    def __init__(
        self,
        base,
        feature_dim: int,
        out_dim: int,
        *,
        name: str = "default",
        lam: float = 1e-3,
        chunk: int = 64,
        holdout_every: int = 8,
        holdout_cap: int = 512,
        metrics=None,  # LifecycleMetrics; duck-typed
    ):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self._base = base
        self.name = name
        self.lam = float(lam)
        self.chunk = int(chunk)
        self.out_dim = int(out_dim)
        self._holdout_every = max(0, int(holdout_every))
        self._holdout_cap = int(holdout_cap)
        self._metrics = metrics
        self._lock = threading.Lock()
        d = int(feature_dim) + 1  # augmented with the bias column
        self._G = jnp.zeros((d, d), jnp.float32)  # guarded-by: _lock
        self._AY = jnp.zeros((d, out_dim), jnp.float32)  # guarded-by: _lock
        self._n = 0  # guarded-by: _lock
        self._seen = 0  # guarded-by: _lock
        self._hold_x: list = []  # guarded-by: _lock
        self._hold_y: list = []  # guarded-by: _lock

    # -- accumulation ------------------------------------------------------

    @property
    def n_accumulated(self) -> int:
        with self._lock:
            return self._n

    @property
    def n_holdout(self) -> int:
        with self._lock:
            return len(self._hold_x)

    def add(self, instances: Any, labels: Any) -> int:
        """Fold one labeled batch in. Returns the rows ACCUMULATED
        (holdout-diverted rows don't count). Chunk-size independent:
        any split of the same rows lands on the same ``(G, AY, n)``."""
        X = np.asarray(instances, np.float32)
        Y = np.asarray(labels, np.float32)
        if X.ndim != 2 or Y.ndim != 2 or X.shape[0] != Y.shape[0]:
            raise ValueError(
                f"need matching 2-D instances/labels, got {X.shape} "
                f"vs {Y.shape}"
            )
        if Y.shape[1] != self.out_dim:
            raise ValueError(
                f"labels are {Y.shape[1]}-dim, model serves "
                f"{self.out_dim}"
            )
        with self._lock:
            # split the holdout rows out FIRST (a global every-k-th
            # row counter), so the accuracy gate's data never touches
            # the normal equations — poisoned or not
            idx = np.arange(X.shape[0]) + self._seen
            self._seen += X.shape[0]
            if self._holdout_every > 0:
                hold = (idx % self._holdout_every) == 0
            else:
                hold = np.zeros(X.shape[0], bool)
            # cap the buffer; hold-pattern rows past the cap fold
            # into the normal equations like any other row (labels
            # are scarce — none get dropped)
            room = max(0, self._holdout_cap - len(self._hold_x))
            kept = np.where(hold)[0][:room]
            for xi, yi in zip(X[kept], Y[kept]):
                self._hold_x.append(xi)
                self._hold_y.append(yi)
            keep = np.ones(X.shape[0], bool)
            keep[kept] = False
            X, Y = X[keep], Y[keep]
            accumulated = int(X.shape[0])
            for start in range(0, X.shape[0], self.chunk):
                self._accumulate_chunk_locked(
                    X[start:start + self.chunk],
                    Y[start:start + self.chunk],
                )
        return accumulated

    def _accumulate_chunk_locked(
        self, xs: np.ndarray, ys: np.ndarray
    ) -> None:
        n = xs.shape[0]
        if n == 0:
            return
        # chaos point: an armed lifecycle.refit.poison corrupts THIS
        # chunk's targets before they fold into (G, AY) — the model
        # the next solve produces is garbage while the holdout buffer
        # (split off above) stays clean, so the accuracy gate must
        # catch it and the controller must roll back. Unarmed: one
        # attribute read, the ctx dict is never built.
        poisoned = faults.armed() and faults.fire(
            "lifecycle.refit.poison", {"model": self.name}
        ) is not None
        pad = self.chunk - n
        if pad:
            xs = np.concatenate(
                [xs, np.zeros((pad, xs.shape[1]), np.float32)]
            )
            ys = np.concatenate(
                [ys, np.zeros((pad, ys.shape[1]), np.float32)]
            )
        mask = np.zeros(self.chunk, np.float32)
        mask[:n] = 1.0
        z = np.arctanh(np.clip(ys, -_CLIP, _CLIP))
        if poisoned:
            z = -40.0 * z
        H = np.asarray(self._base._batch_run(jnp.asarray(xs)))[
            : self.chunk
        ]
        self._G, self._AY = _accum_update(
            self._G, self._AY, jnp.asarray(H), jnp.asarray(z),
            jnp.asarray(mask),
        )
        self._n += n
        if self._metrics is not None:
            self._metrics.record_refit_chunk(n)

    # -- solve / holdout ---------------------------------------------------

    def solve(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One ridge solve over the running state -> ``(W, b)`` for a
        candidate head. Raises if nothing was accumulated yet."""
        with self._lock:
            if self._n == 0:
                raise RuntimeError("no feedback accumulated yet")
            W_aug = _jit_psd_solve(
                self._G, self._AY, jnp.float32(self.lam * self._n)
            )
        W_aug.block_until_ready()
        return W_aug[:-1], W_aug[-1]

    def holdout_errors(
        self, candidate, incumbent
    ) -> Tuple[Optional[float], Optional[float]]:
        """Held-out MSE of two full fitted pipelines (raw instances
        in, served outputs out). ``(None, None)`` until the holdout
        buffer has samples."""
        with self._lock:
            if not self._hold_x:
                return None, None
            X = np.stack(self._hold_x)
            Y = np.stack(self._hold_y)
        out = []
        for fitted in (candidate, incumbent):
            pred = np.asarray(fitted._batch_run(jnp.asarray(X)))[
                : X.shape[0]
            ]
            out.append(float(np.mean((pred - Y) ** 2)))
        return out[0], out[1]

    # -- rollback support --------------------------------------------------

    def snapshot(self) -> tuple:
        """The accumulated state at solve time — ``restore`` discards
        everything folded in since (a poisoned cycle must not leak
        into the NEXT candidate)."""
        with self._lock:
            return (self._G, self._AY, self._n, self._seen)

    def restore(self, snap: tuple) -> None:
        with self._lock:
            self._G, self._AY, self._n, self._seen = snap


__all__ = ["RefitAccumulator"]
