"""Distributed full-batch L-BFGS with L2 regularization.

Reference: nodes/learning/LBFGS.scala — per-partition gradients over
partition-stacked matrices, treeReduce sum, Breeze LBFGS driver on the
master; nodes/learning/Gradient.scala for the least-squares gradients.

TPU-native split: the O(n·d·k) value-and-gradient is ONE jitted program
over the sharded feature matrix (per-shard MXU matmuls + psum over "data"
— the treeReduce). Two optimizer drivers: the default fused device
driver (``run_lbfgs_device`` — the ENTIRE optimization, two-loop
recursion + Armijo line search + convergence test, is one
``lax.while_loop`` program with zero host syncs), and the f64 host
driver (``run_lbfgs``, the Breeze-driver equivalent) for problems that
need double-precision history.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from keystone_tpu.ops.learning.cost import CostModel
from keystone_tpu.ops.learning.linear import LinearMapper, SparseLinearMapper
from keystone_tpu.ops.stats.nodes import StandardScaler
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import LabelEstimator


class Gradient:
    """loss(W; A, b) total + gradient over a batch (reference:
    nodes/learning/Gradient.scala:10).

    Gradients are stateless, so equality/hash are type-based — this makes
    ``regularized_vg`` bound methods from different instances of the same
    gradient class hit the same jit cache entry in the fused driver
    (fresh estimators per fit would otherwise recompile the optimizer).
    """

    def __eq__(self, other):
        return type(self) is type(other)

    def __hash__(self):
        return hash(type(self))

    def value_and_grad(self, A, b, W) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def regularized_vg(self, W, A, b, reg, n):
        """Mean loss + L2, in the ``vg(W, *data)`` shape the fused device
        driver consumes (bound method: stable jit cache key per gradient
        instance)."""
        loss, g = self.value_and_grad(A, b, W)
        return (
            loss / n + 0.5 * reg * jnp.sum(W * W),
            g / n + reg * W,
        )


class LeastSquaresDenseGradient(Gradient):
    """0.5·‖AW − b‖² summed over examples; grad = Aᵀ(AW − b)
    (reference: Gradient.scala:29)."""

    def value_and_grad(self, A, b, W):
        # HIGHEST for f32 inputs — TPU DEFAULT truncates f32 matmul
        # operands to bf16 (see block_ls._f32_mm); bf16 data keeps the
        # native MXU path
        hp = (
            jax.lax.Precision.HIGHEST
            if A.dtype == jnp.float32
            else None
        )
        res = (
            jax.lax.dot_general(
                A, W, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hp,
            )
            - b
        )
        loss = 0.5 * jnp.sum(res * res)
        grad = jax.lax.dot_general(
            A.T, res, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=hp,
        )
        return loss, grad


class LeastSquaresSparseGradient(Gradient):
    """Same objective with a BCOO feature matrix (reference:
    Gradient.scala:58 — hand-rolled sparse loops; here BCOO dot_generals
    that XLA lowers to gather/scatter kernels)."""

    def value_and_grad(self, A, b, W):
        res = jsparse.bcoo_dot_general(
            A, W, dimension_numbers=(([1], [0]), ([], []))
        ) - b
        loss = 0.5 * jnp.sum(res * res)
        grad = jsparse.bcoo_dot_general(
            A, res, dimension_numbers=(([0], [0]), ([], []))
        )
        return loss, grad


def run_lbfgs_device(
    device_vg: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
    w0: jnp.ndarray,
    num_iterations: int,
    num_corrections: int = 10,
    convergence_tol: float = 1e-4,
    data: tuple = (),
):
    """The ENTIRE L-BFGS optimization as one device program: two-loop
    recursion over a ring-buffered (m, ...) history, Armijo backtracking
    via ``lax.while_loop``, convergence test in-graph. Zero host syncs —
    where the host driver (``run_lbfgs``) pays a dispatch round trip per
    line-search trial, this pays one per *fit*. f32 on device (the host
    driver is the f64 fallback for ill-conditioned problems).

    ``device_vg``: traceable ``(W, *data) -> (loss, grad)`` with ``W``
    in its natural (d, k) shape. It is a STATIC jit argument — pass a
    module-level function or bound method (not a fresh lambda) with the
    arrays in ``data``, or every call re-traces and re-compiles the
    whole nested-loop program (~70 s of XLA compile measured).

    Returns ``(W, iterations, evaluations)``, the last two int32 device
    scalars the program counts (iterations taken; calls of
    ``device_vg``, the first included): ``count_lbfgs`` adds them to the
    registry, reading them once the fit is done.
    """
    return _lbfgs_device_run(
        device_vg, num_iterations, num_corrections,
        jnp.float32(convergence_tol), jnp.asarray(w0, jnp.float32), *data
    )


def count_lbfgs(iterations, evaluations) -> None:
    """Add one fused fit's iteration and evaluation counts (device
    scalars from ``run_lbfgs_device``: reading them waits for the fit) to ``keystone_solver_lbfgs_iterations_total`` and
    ``keystone_solver_lbfgs_evaluations_total``."""
    from keystone_tpu.observability.registry import get_global_registry

    reg = get_global_registry()
    reg.counter(
        "keystone_solver_lbfgs_iterations_total",
        "iterations of fused device L-BFGS fits",
    ).inc(by=int(iterations))
    reg.counter(
        "keystone_solver_lbfgs_evaluations_total",
        "loss-and-gradient evaluations of fused device L-BFGS fits, "
        "line-search trials and the first included",
    ).inc(by=int(evaluations))


@partial(
    jax.jit, static_argnames=("device_vg", "num_iterations", "m")
)
def _lbfgs_device_run(
    device_vg, num_iterations: int, m: int, convergence_tol, w0, *data
):
    shape = w0.shape

    def dot(a, b):
        return jnp.sum(a * b)

    def vg(w):
        return device_vg(w, *data)

    f0, g0 = vg(w0)
    S = jnp.zeros((m,) + shape, jnp.float32)
    Y = jnp.zeros((m,) + shape, jnp.float32)

    def cond(st):
        it, w, f, g, S, Y, count, done, _ = st
        return (it < num_iterations) & ~done

    def body(st):
        it, w, f, g, S, Y, count, done, evals = st
        n_hist = jnp.minimum(count, m)

        # two-loop recursion (ring buffer, newest first)
        def loop1(i, carry):
            q, alphas = carry
            j = (count - 1 - i) % m
            valid = i < n_hist
            s, y = S[j], Y[j]
            rho = 1.0 / jnp.where(valid, dot(y, s), 1.0)
            a = jnp.where(valid, rho * dot(s, q), 0.0)
            return q - a * y, alphas.at[i].set(a)

        q, alphas = jax.lax.fori_loop(
            0, m, loop1, (g, jnp.zeros((m,), jnp.float32))
        )
        jl = (count - 1) % m
        gamma = jnp.where(
            count > 0,
            dot(S[jl], Y[jl]) / jnp.maximum(dot(Y[jl], Y[jl]), 1e-30),
            1.0,
        )
        q = q * gamma

        def loop2(i2, q):
            i = m - 1 - i2
            j = (count - 1 - i) % m
            valid = i < n_hist
            s, y = S[j], Y[j]
            rho = 1.0 / jnp.where(valid, dot(y, s), 1.0)
            b = jnp.where(valid, rho * dot(y, q), 0.0)
            return q + (alphas[i] - b) * s

        q = jax.lax.fori_loop(0, m, loop2, q)

        direction = -q
        dg = dot(direction, g)
        bad = dg >= 0
        direction = jnp.where(bad, -g, direction)
        dg = jnp.where(bad, -dot(g, g), dg)

        # Armijo backtracking: state carries the step to try next
        def ls_cond(ls):
            step, f_t, g_t, w_t, ok, tries = ls
            return ~ok & (tries < 30)

        def ls_body(ls):
            step, _, _, _, _, tries = ls
            w_try = w + step * direction
            f_try, g_try = vg(w_try)
            ok = f_try <= f + 1e-4 * step * dg
            return (
                jnp.where(ok, step, step * 0.5),
                f_try, g_try, w_try, ok, tries + 1,
            )

        _, f_new, g_new, w_new, ok, tries = jax.lax.while_loop(
            ls_cond, ls_body,
            (jnp.float32(1.0), f, g, w, jnp.bool_(False), 0),
        )

        s_vec = w_new - w
        y_vec = g_new - g
        store = ok & (dot(s_vec, y_vec) > 1e-10)
        j = count % m
        S = jnp.where(store, S.at[j].set(s_vec), S)
        Y = jnp.where(store, Y.at[j].set(y_vec), Y)
        count = count + jnp.where(store, 1, 0)

        improvement = jnp.abs(f - f_new) / jnp.maximum(
            jnp.maximum(jnp.abs(f), jnp.abs(f_new)), 1.0
        )
        done = ~ok | (improvement < convergence_tol)
        keep = lambda new, old: jnp.where(ok, new, old)
        return (
            it + 1, keep(w_new, w), keep(f_new, f), keep(g_new, g),
            S, Y, count, done, evals + tries,
        )

    st = (jnp.int32(0), w0, f0, g0, S, Y, jnp.int32(0),
          jnp.bool_(False), jnp.int32(1))
    it, w, _, _, _, _, _, _, evals = jax.lax.while_loop(cond, body, st)
    return w, it, evals


def run_lbfgs(
    value_and_grad: Callable[[np.ndarray], Tuple[float, np.ndarray]],
    w0: np.ndarray,
    num_iterations: int,
    num_corrections: int = 10,
    convergence_tol: float = 1e-4,
) -> np.ndarray:
    """Two-loop-recursion L-BFGS with Armijo backtracking, host f64
    (the Breeze LBFGS driver stand-in, LBFGS.scala:135)."""
    w = w0.astype(np.float64).ravel()
    f, g = value_and_grad(w)
    s_hist: list = []
    y_hist: list = []
    for _ in range(num_iterations):
        # two-loop recursion
        q = g.copy()
        alphas = []
        for s, y in reversed(list(zip(s_hist, y_hist))):
            rho = 1.0 / (y @ s)
            a = rho * (s @ q)
            alphas.append((a, rho, s, y))
            q -= a * y
        if y_hist:
            y = y_hist[-1]
            s = s_hist[-1]
            q *= (s @ y) / (y @ y)
        for a, rho, s, y in reversed(alphas):
            b = rho * (y @ q)
            q += (a - b) * s
        direction = -q
        # backtracking Armijo line search
        step = 1.0
        dg = direction @ g
        if dg >= 0:  # not a descent direction; reset
            direction = -g
            dg = -(g @ g)
        f_new, g_new, w_new = f, g, w
        for _ in range(30):
            w_try = w + step * direction
            f_try, g_try = value_and_grad(w_try)
            if f_try <= f + 1e-4 * step * dg:
                f_new, g_new, w_new = f_try, g_try, w_try
                break
            step *= 0.5
        else:
            break  # line search failed
        s_vec = w_new - w
        y_vec = g_new - g
        if s_vec @ y_vec > 1e-10:
            s_hist.append(s_vec)
            y_hist.append(y_vec)
            if len(s_hist) > num_corrections:
                s_hist.pop(0)
                y_hist.pop(0)
        improvement = abs(f - f_new) / max(abs(f), abs(f_new), 1.0)
        w, f, g = w_new, f_new, g_new
        if improvement < convergence_tol:
            break
    return w


@dataclasses.dataclass(eq=False)
class LBFGSwithL2(LabelEstimator, CostModel):
    """min_W (1/n)·Σ loss(W; a_i, b_i) + 0.5·λ‖W‖²
    (reference: LBFGS.scala:14). ``fit_intercept`` mean-centers via
    StandardScaler like the reference (:150-166)."""

    gradient: Gradient = dataclasses.field(
        default_factory=LeastSquaresDenseGradient
    )
    fit_intercept: bool = True
    num_corrections: int = 10
    convergence_tol: float = 1e-4
    num_iterations: int = 20
    reg_param: float = 0.0
    sparse: bool = False
    driver: str = "device"  # "device": whole optimization fused in one
    # program, zero host syncs (run_lbfgs_device) | "host": f64 Breeze-
    # driver equivalent, one device round trip per line-search trial

    def fit(self, data: Dataset, labels: Dataset):
        if self.driver not in ("device", "host"):
            raise ValueError(f"driver must be 'device' or 'host', got {self.driver!r}")
        data = data.to_array_mode()
        labels = labels.to_array_mode()
        A = data.padded()
        b = labels.padded()
        is_sparse = isinstance(A, jsparse.BCOO)
        d = A.shape[1]
        k = b.shape[1]
        n = data.n

        feat_scaler = label_scaler = None
        if self.fit_intercept and not is_sparse:
            feat_scaler = StandardScaler(normalize_std_dev=False).fit(data)
            label_scaler = StandardScaler(normalize_std_dev=False).fit(labels)
            data = feat_scaler.apply_batch(data)
            labels = label_scaler.apply_batch(labels)
            A = data.padded()
            b = labels.padded()

        grad_fn = self.gradient

        @jax.jit
        def device_vg(A, b, W):
            loss, g = grad_fn.value_and_grad(A, b, W)
            return (
                loss / n + 0.5 * self.reg_param * jnp.sum(W * W),
                g / n + self.reg_param * W,
            )

        if self.driver == "device":
            W, iterations, evaluations = run_lbfgs_device(
                self.gradient.regularized_vg,  # bound method: stable key
                jnp.zeros((d, k), jnp.float32),
                self.num_iterations,
                self.num_corrections,
                self.convergence_tol,
                data=(A, b, jnp.float32(self.reg_param), jnp.float32(n)),
            )
            count_lbfgs(iterations, evaluations)
        else:
            def vg(w_flat: np.ndarray):
                W = jnp.asarray(
                    w_flat.reshape(d, k).astype(np.float32)
                )
                loss, g = device_vg(A, b, W)
                return float(loss), np.asarray(g, np.float64).ravel()

            w = run_lbfgs(
                vg,
                np.zeros((d, k)),
                self.num_iterations,
                self.num_corrections,
                self.convergence_tol,
            )
            W = jnp.asarray(w.reshape(d, k).astype(np.float32))
        if is_sparse:
            return SparseLinearMapper(W)
        if self.fit_intercept:
            # reference: LinearMapper(model, Some(labelScaler.mean),
            # Some(featureScaler)) — center input, add back label mean
            return LinearMapper(
                W, intercept=label_scaler.mean, feature_scaler=feat_scaler
            )
        return LinearMapper(W)

    @property
    def weight(self) -> int:
        # reference: LBFGS.scala weight = numIterations + 1
        return self.num_iterations + 1


@dataclasses.dataclass(eq=False)
class DenseLBFGSwithL2(LBFGSwithL2):
    """Dense-gradient variant (reference: LBFGS.scala:135); cost model from
    :175-191."""

    def cost(self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
             network_weight):
        flops = n * float(d) * k / num_machines
        bytes_scanned = n * float(d) / num_machines
        network = 2.0 * d * k * max(np.log2(num_machines), 1.0)
        return self.num_iterations * (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


@dataclasses.dataclass(eq=False)
class SparseLBFGSwithL2(LBFGSwithL2):
    """Sparse-gradient variant (reference: LBFGS.scala:208); cost model
    from :264-280 (sparseOverhead ~ 3x the dense per-element cost)."""

    sparse_overhead: float = 3.0

    def __post_init__(self):
        self.gradient = LeastSquaresSparseGradient()
        self.fit_intercept = False
        self.sparse = True

    def cost(self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
             network_weight):
        flops = n * sparsity * float(d) * k / num_machines
        bytes_scanned = n * float(d) * sparsity / num_machines
        network = 2.0 * d * k * max(np.log2(num_machines), 1.0)
        return self.num_iterations * (
            self.sparse_overhead
            * max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )
