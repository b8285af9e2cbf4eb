"""The block Gram from its upper block triangle (``block_ls._sym_gram``):
the same matrix as the one full product, exactly symmetric, at the
applications' widths, sharded and not, and counted as it is cut."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.learning import block_ls


def rows(n, width, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, width)).astype(np.float32) + 0.5
    return jnp.asarray(X).astype(dtype)


def dots(width, dtype=jnp.float32):
    """(left width, right width) of each product ``_sym_gram`` traces."""
    jaxpr = jax.make_jaxpr(block_ls._sym_gram)(
        jax.ShapeDtypeStruct((8, width), dtype))
    return [tuple(e.outvars[0].aval.shape) for e in jaxpr.eqns
            if e.primitive.name == "dot_general"]


# the applications' block, RandomPatchCifar's last block (17 x 128), a
# width off the MXU's 128, and one cut of a block just over the leaf
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [4096, 2176, 1000, 640])
def test_equals_the_full_product_and_is_exactly_symmetric(width, dtype):
    X = rows(96, width, dtype)
    G = np.asarray(jax.jit(block_ls._sym_gram)(X))
    full = np.asarray(block_ls._f32_mm(X.T, X))
    assert G.dtype == np.float32 and G.shape == (width, width)
    # every entry is the same dot product over the same rows
    np.testing.assert_allclose(G, full, rtol=2e-6, atol=2e-6 * 96)
    assert np.array_equal(G, G.T)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [block_ls._GRAM_LEAF, 384, 10])
def test_at_or_under_the_leaf_it_is_the_one_full_product(width, dtype):
    assert block_ls._gram_cut(width) == 0
    assert dots(width, dtype) == [(width, width)]
    X = rows(64, width, dtype)
    assert np.array_equal(np.asarray(block_ls._sym_gram(X)),
                          np.asarray(block_ls._f32_mm(X.T, X)))


def test_f32_products_stay_at_highest_and_bf16_on_the_native_path():
    for dtype, precision in [(jnp.float32, "HIGHEST"), (jnp.bfloat16, None)]:
        jaxpr = jax.make_jaxpr(block_ls._sym_gram)(
            jax.ShapeDtypeStruct((8, 1024), dtype))
        found = [e.params["precision"] for e in jaxpr.eqns
                 if e.primitive.name == "dot_general"]
        assert len(found) == 3
        for p in found:
            assert (p is None) if precision is None else (
                precision in str(p))


@pytest.mark.parametrize("width,cut,products,share", [
    (10, 0, 1, 1.0),
    (512, 0, 1, 1.0),
    (640, 384, 3, (384 * 384 + 384 * 256 + 256 * 256) / 640 ** 2),
    (1024, 512, 3, 0.75),
    (2176, 1152, 9, 2867200 / 2176 ** 2),
    (4096, 2048, 15, 0.5625),
])
def test_the_count_follows_the_cuts_the_helper_makes(
        width, cut, products, share):
    assert block_ls._gram_cut(width) == cut
    assert cut % 128 == 0
    computed, whole = block_ls._gram_pairs(width)
    assert whole == width * width
    assert computed / whole == pytest.approx(share, abs=1e-12)
    traced = dots(width)
    assert len(traced) == products
    assert sum(a * b for a, b in traced) == computed


@pytest.mark.needs_mesh8
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [4096, 2176])
def test_rows_sharded_over_the_mesh(mesh8, width, dtype):
    """Per-shard products and a psum over the data axis each: no row is
    gathered, and the result is the unsharded one."""
    from keystone_tpu.parallel import mesh as mesh_lib

    X = rows(128, width, dtype, seed=1)
    Xs = jax.device_put(X, mesh_lib.data_sharding(mesh8))
    gram = jax.jit(block_ls._sym_gram)
    text = gram.lower(Xs).compile().as_text()
    assert "all-reduce" in text and "all-gather" not in text
    G = np.asarray(gram(Xs))
    np.testing.assert_allclose(
        G, np.asarray(block_ls._f32_mm(X.T, X)), rtol=2e-6, atol=3e-4)
    assert np.array_equal(G, G.T)
