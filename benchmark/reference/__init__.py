"""Plain references, one per configuration (named by its ``reference``
key). They import nothing of keystone_tpu and take nothing it made."""

import numpy as np


def rel_err(got, want) -> float:
    """Relative Frobenius error; infinite where the shapes differ or
    ``got`` is not finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
