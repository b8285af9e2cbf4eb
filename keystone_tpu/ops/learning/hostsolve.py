"""Host-side f64 solves for small regularized PSD systems.

The reference's block solvers compute Gram matrices on executors but solve
the (b, b) systems on the driver in double precision (mlmatrix
NormalEquations / BlockCoordinateDescent; nodes/learning/
BlockLinearMapper.scala:234-240). TPUs have no native f64, and these
systems are genuinely ill-conditioned (n < b blocks with tiny λ), beyond
f32 Cholesky's eps. Same split here: the O(n·b²) Gram work stays on device
in f32; the O(b³) solve of a matrix that already fits on one host runs in
numpy f64. Transfers are (b,b)+(b,k) — negligible next to the Gram pass.

The two phases carry spans (``solver.readback``: the read-back waits for
the device to finish the program that made the arrays, and for their copy
to the host; ``solver.host_solve``: the factorisation and the solve) and
counters (``keystone_solver_readback_bytes_total``, ``_host_solves_total``,
``_host_solve_fallbacks_total``), so a profiler trace and a scrape say
what the chip waited for.

The solve has two halves: ``_factor`` (ridge + ``cho_factor``, ``eigh``
where Cholesky breaks down) gives a ``HostFactor``, and
``HostFactor.solve`` solves a right-hand side against it.
``psd_solve_host`` is the two composed. A caller that meets the same
matrix again (block coordinate descent: a block's Gram is the same in
every sweep) keeps the factor and later calls
``psd_solve_factored_host`` with the right-hand side alone. The phases
can be placed apart: ``read_back`` copies a Gram and its right-hand side
to the host, and ``factor_solve_host`` factors and solves what came back,
each in one span of its phase. ``read_back_ahead`` starts a Gram's read-back
before its turn: the copy to the host, then the float64 conversion on the
read-back thread (16.8 M entries at a block of 4,096: ~130 ms on the v5e
host, where the copy itself is ~20 ms), and the device array deleted once
the host has it. The block loop calls it for the next block's Gram
between this block's read-back and its factorisation, which releases the
GIL, so all of it runs under the ``cho_factor``; ``read_back`` then takes
the handle in the Gram's place and waits only for what is left.
"""

from __future__ import annotations

from concurrent.futures import Future, ThreadPoolExecutor
from typing import NamedTuple, Tuple

import jax
import numpy as np
import scipy.linalg

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span


class HostFactor(NamedTuple):
    """The float64 factor of one ridged (b, b) system, in the form that
    was taken when it was made, so every solve against it goes the same
    way: ``(c, low)`` as ``cho_factor`` wrote it, or ``(w, V)`` of the
    ``eigh`` fall-back with ``w`` already clamped."""

    form: str  # "cholesky" | "eigh"
    parts: tuple

    def solve(self, R: np.ndarray) -> np.ndarray:
        if self.form == "cholesky":
            return scipy.linalg.cho_solve(self.parts, R, check_finite=False)
        w, V = self.parts
        return V @ ((V.T @ R) / w[:, None])


def read_back(*arrays) -> list:
    """Float64 host copies of ``arrays``; counts the bytes that came
    from the device. An array may be ``read_back_ahead``'s handle: its
    copy is what that read-back made, waited for here."""
    with span("solver.readback"):
        out = [
            a.result() if isinstance(a, Future)
            else np.asarray(a, dtype=np.float64)
            for a in arrays
        ]
    _count_readback(*(a for a in arrays if isinstance(a, jax.Array)))
    return out


_ahead = None  # the read-back thread, made on first use


def read_back_ahead(gram: jax.Array) -> Future:
    """Start ``gram``'s copy to the host, and its conversion to float64
    on the read-back thread; ``read_back`` takes the returned handle in
    its place. The caller hands the array over: it is deleted on the
    device once the host has it."""
    global _ahead
    if _ahead is None:
        _ahead = ThreadPoolExecutor(1, thread_name_prefix="solver-readback")
    gram.copy_to_host_async()
    _count_readback(gram)
    return _ahead.submit(_to_host_f64, gram)


def _to_host_f64(gram: jax.Array) -> np.ndarray:
    G = np.asarray(gram, dtype=np.float64)
    gram.delete()
    return G


def _count_readback(*arrays) -> None:
    get_global_registry().counter(
        "keystone_solver_readback_bytes_total",
        "bytes of Gram and right-hand side read back for host solves",
    ).inc(by=sum(a.nbytes for a in arrays))


def _count_solve() -> None:
    get_global_registry().counter(
        "keystone_solver_host_solves_total",
        "(b, b) systems solved on the host in float64",
    ).inc()


def _factor(G: np.ndarray, lam: float, sp) -> HostFactor:
    """Factor (G + lam·I); robust to indefiniteness from f32 rounding
    (falls back to eigh with eigenvalue clamping, noted on ``sp``)."""
    if lam:
        G = G + lam * np.eye(G.shape[0])
    try:
        return HostFactor(
            "cholesky", scipy.linalg.cho_factor(G, check_finite=False)
        )
    except np.linalg.LinAlgError:
        sp.set_attr("fallback", "eigh")
        get_global_registry().counter(
            "keystone_solver_host_solve_fallbacks_total",
            "host solves that fell back from Cholesky to eigh",
        ).inc()
        w, V = np.linalg.eigh(G)
        w = np.maximum(w, 1e-12 * max(w.max(), 1.0))
        return HostFactor("eigh", (w, V))


def factor_solve_host(
    G: np.ndarray, R: np.ndarray, lam: float = 0.0
) -> Tuple[np.ndarray, HostFactor]:
    """Solve (G + lam·I) X = R in f64 on host arrays (``read_back``'s),
    and hand back the factor with the solution for later right-hand sides
    of the same matrix (``psd_solve_factored_host``)."""
    _count_solve()
    with span("solver.host_solve", width=G.shape[0]) as sp:
        factor = _factor(G, lam, sp)
        return factor.solve(R), factor


def psd_solve_factored_host(factor: HostFactor, rhs) -> np.ndarray:
    """Solve against a factor kept from ``factor_solve_host``: only the
    right-hand side is read back, nothing is factored."""
    (R,) = read_back(rhs)
    _count_solve()
    with span("solver.host_solve", width=R.shape[0], factor="kept"):
        return factor.solve(R)


def psd_solve_host(gram, rhs, lam: float = 0.0) -> np.ndarray:
    """Solve (gram + lam·I) X = rhs in f64 on host; robust to indefiniteness
    from f32 rounding (falls back to eigh with eigenvalue clamping)."""
    return factor_solve_host(*read_back(gram, rhs), lam)[0]
