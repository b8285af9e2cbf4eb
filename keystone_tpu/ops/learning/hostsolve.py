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
the device to finish the Gram; ``solver.host_solve``) and counters
(``keystone_solver_readback_bytes_total``, ``_host_solves_total``,
``_host_solve_fallbacks_total``), so a profiler trace and a scrape say
what the chip waited for.
"""

from __future__ import annotations

import jax
import numpy as np
import scipy.linalg

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span


def psd_solve_host(gram, rhs, lam: float = 0.0) -> np.ndarray:
    """Solve (gram + lam·I) X = rhs in f64 on host; robust to indefiniteness
    from f32 rounding (falls back to eigh with eigenvalue clamping)."""
    reg = get_global_registry()
    with span("solver.readback"):
        G = np.asarray(gram, dtype=np.float64)
        R = np.asarray(rhs, dtype=np.float64)
    reg.counter(
        "keystone_solver_readback_bytes_total",
        "bytes of Gram and right-hand side read back for host solves",
    ).inc(by=sum(
        x.nbytes for x in (gram, rhs) if isinstance(x, jax.Array)
    ))
    reg.counter(
        "keystone_solver_host_solves_total",
        "(b, b) systems solved on the host in float64",
    ).inc()
    with span("solver.host_solve", width=G.shape[0]) as sp:
        if lam:
            G = G + lam * np.eye(G.shape[0])
        try:
            c, low = scipy.linalg.cho_factor(G, check_finite=False)
            return scipy.linalg.cho_solve((c, low), R, check_finite=False)
        except np.linalg.LinAlgError:
            sp.set_attr("fallback", "eigh")
            reg.counter(
                "keystone_solver_host_solve_fallbacks_total",
                "host solves that fell back from Cholesky to eigh",
            ).inc()
            w, V = np.linalg.eigh(G)
            w = np.maximum(w, 1e-12 * max(w.max(), 1.0))
            return V @ ((V.T @ R) / w[:, None])
