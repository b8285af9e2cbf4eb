"""The default device solve of the block solver has to compile at the
applications' block widths: no eigh fall-back under lax.cond from a few
thousand columns up (PERF.md section 7, fault 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.learning import block_ls


def solve_jaxpr(width: int, classes: int = 10) -> str:
    a = jax.ShapeDtypeStruct((width, width), jnp.float32)
    rhs = jax.ShapeDtypeStruct((width, classes), jnp.float32)
    return str(jax.make_jaxpr(block_ls._psd_solve_with_factor)(a, a, rhs))


# RandomPatchCifar's last block and the applications' block width
@pytest.mark.parametrize("width", [2176, 4096])
def test_no_eigh_at_the_applications_widths(width):
    text = solve_jaxpr(width)
    assert "eigh" not in text and "cond" not in text
    assert "triangular_solve" in text


@pytest.mark.parametrize("width", [64, block_ls._EIGH_FALLBACK_MAX_WIDTH])
def test_small_widths_keep_the_fallback(width):
    text = solve_jaxpr(width)
    assert "eigh" in text and "cond" in text


def test_breakdown_surfaces_as_a_non_finite_model(monkeypatch):
    """Without the fall-back an indefinite system gives a model that is
    not finite, which wide callers assert on."""
    monkeypatch.setattr(block_ls, "_EIGH_FALLBACK_MAX_WIDTH", 4)
    a = -jnp.eye(8, dtype=jnp.float32)
    w = block_ls._psd_solve_device(a, jnp.ones((8, 2), jnp.float32), 0.0)
    assert not np.all(np.isfinite(np.asarray(w)))
    good = block_ls._psd_solve_device(
        jnp.eye(8, dtype=jnp.float32) * 4.0, jnp.ones((8, 2), jnp.float32), 0.0)
    np.testing.assert_allclose(np.asarray(good), 0.25, rtol=1e-6)
