"""Image node tests (reference: ConvolverSuite vs a SciPy-generated
reference, PoolerSuite, WindowerSuite)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from keystone_tpu.ops.images import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
    Windower,
    channel_major_vectorize,
    pack_filters,
)
from keystone_tpu.ops.learning import ZCAWhitenerEstimator
from keystone_tpu.parallel.dataset import Dataset


def _naive_convolver(img, filters_packed, k, C, normalize, whitener, var_c):
    """Direct translation of Convolver.makePatches + GEMM
    (Convolver.scala:128-205)."""
    X, Y = img.shape[0], img.shape[1]
    rw, rh = X - k + 1, Y - k + 1
    patch_mat = np.zeros((rw * rh, k * k * C))
    for poy in range(k):
        for pox in range(k):
            for y in range(rh):
                for x in range(rw):
                    for c in range(C):
                        px = c + pox * C + poy * C * k
                        py = x + y * rw
                        patch_mat[py, px] = img[x + pox, y + poy, c]
    if normalize:
        means = patch_mat.mean(1)
        var = ((patch_mat - means[:, None]) ** 2).sum(1) / (
            patch_mat.shape[1] - 1
        )
        sds = np.sqrt(var + var_c)
        patch_mat = (patch_mat - means[:, None]) / sds[:, None]
    if whitener is not None:
        patch_mat = patch_mat - np.asarray(whitener.means)[None, :]
    conv = patch_mat @ filters_packed.T  # (rw*rh, F)
    # result image is RowMajor(resWidth, resHeight, F): idx = f + y*F + x*F*rh?
    # RowMajorArrayVectorizedImage: data[f + c-major...]; we only compare
    # values per (x, y, f) by reshaping fortran-style over (x, y)
    return conv.reshape(rh, rw, -1).transpose(1, 0, 2)  # wait: py = x + y*rw


def test_convolver_matches_naive():
    rng = np.random.default_rng(0)
    k, C, F = 3, 2, 4
    img = rng.standard_normal((8, 7, C)).astype(np.float32)
    filters = rng.standard_normal((F, k * k * C)).astype(np.float32)
    conv = Convolver(
        jnp.asarray(filters), 8, 7, C, normalize_patches=False
    )
    got = np.asarray(conv.apply(jnp.asarray(img)))
    naive = _naive_convolver(img, filters, k, C, False, None, 10.0)
    # naive is (rw, rh, F) after transpose — compare elementwise
    assert got.shape == (6, 5, F)
    np.testing.assert_allclose(got, naive, atol=1e-3)


def test_convolver_normalized_matches_naive():
    rng = np.random.default_rng(1)
    k, C, F = 3, 3, 5
    img = (rng.uniform(0, 1, (9, 9, C))).astype(np.float32)
    filters = rng.standard_normal((F, k * k * C)).astype(np.float32)
    conv = Convolver(
        jnp.asarray(filters), 9, 9, C, normalize_patches=True,
        var_constant=10.0,
    )
    got = np.asarray(conv.apply(jnp.asarray(img)))
    naive = _naive_convolver(img, filters, k, C, True, None, 10.0)
    np.testing.assert_allclose(got, naive, atol=1e-3)


def test_convolver_whitened_matches_naive():
    rng = np.random.default_rng(2)
    k, C, F = 2, 2, 3
    img = rng.uniform(0, 1, (6, 6, C)).astype(np.float32)
    filters = rng.standard_normal((F, k * k * C)).astype(np.float32)
    sample = rng.uniform(0, 1, (50, k * k * C)).astype(np.float32)
    whitener = ZCAWhitenerEstimator(eps=0.1).fit_single(jnp.asarray(sample))
    conv = Convolver(
        jnp.asarray(filters), 6, 6, C, whitener=whitener,
        normalize_patches=True,
    )
    got = np.asarray(conv.apply(jnp.asarray(img)))
    naive = _naive_convolver(img, filters, k, C, True, whitener, 10.0)
    np.testing.assert_allclose(got, naive, atol=1e-3)


def test_pooler_matches_reference_loop():
    rng = np.random.default_rng(3)
    img = rng.standard_normal((27, 27, 2)).astype(np.float32)
    pooler = Pooler(stride=13, pool_size=14)
    got = np.asarray(pooler.apply(jnp.asarray(img)))
    # reference loop: strideStart=7; x,y in {7, 20}; window [x-7, min(x+7, 27))
    assert got.shape == (2, 2, 2)
    for i, x in enumerate([7, 20]):
        for j, y in enumerate([7, 20]):
            for c in range(2):
                window = img[x - 7 : min(x + 7, 27), y - 7 : min(y + 7, 27), c]
                np.testing.assert_allclose(
                    got[i, j, c], window.sum(), rtol=1e-5
                )


def test_symmetric_rectifier():
    img = np.array([[[1.0, -2.0]]], np.float32)
    out = np.asarray(SymmetricRectifier(alpha=0.25).apply(jnp.asarray(img)))
    np.testing.assert_allclose(out[0, 0], [0.75, 0.0, 0.0, 1.75])


def test_windower_counts_and_content():
    rng = np.random.default_rng(4)
    imgs = rng.standard_normal((3, 5, 5, 2)).astype(np.float32)
    out = Windower(2, 3).apply(Dataset.of(imgs))
    # (5-3)/2+1 = 2 positions per axis -> 4 windows per image
    assert out.n == 12
    first = np.asarray(out.array())[0]
    np.testing.assert_allclose(first, imgs[0, 0:3, 0:3, :])


def test_patchers():
    rng = np.random.default_rng(5)
    imgs = rng.standard_normal((2, 8, 8, 3)).astype(np.float32)
    cc = CenterCornerPatcher(4, 4, horizontal_flips=True)
    out = cc.apply_batch(Dataset.of(imgs))
    assert out.n == 2 * cc.patches_per_image
    rp = RandomPatcher(3, 4, 4, seed=0)
    out2 = rp.apply_batch(Dataset.of(imgs))
    assert out2.n == 6
    assert np.asarray(out2.array()).shape == (6, 4, 4, 3)


@pytest.mark.parametrize("shape,size", [((5, 9, 9, 3), (4, 4)),
                                        ((3, 8, 11, 2), (8, 5)),
                                        ((4, 7, 6), (3, 6))])
def test_random_patcher_is_the_loop_it_replaced(shape, size):
    """One draw of all origins and one gather on the device give the
    crops that the host loop gave: an ``rng.integers`` call for x and
    one for y, crop after crop, each a slice of its image."""
    imgs = np.random.default_rng(11).standard_normal(shape).astype(np.float32)
    num, (px, py), seed = 3, size, 2147483659
    rng = np.random.default_rng(seed)
    want = []
    for img in imgs:
        for _ in range(num):
            x = rng.integers(0, img.shape[0] - px + 1)
            y = rng.integers(0, img.shape[1] - py + 1)
            want.append(img[x:x + px, y:y + py])
    out = RandomPatcher(num, px, py, seed=seed).apply_batch(Dataset.of(imgs))
    assert out.n == len(want) and out.is_array
    np.testing.assert_array_equal(np.asarray(out.array()), np.stack(want))


def test_vectorizer_channel_major_layout():
    img = np.arange(2 * 3 * 2, dtype=np.float32).reshape(2, 3, 2)
    vec = np.asarray(channel_major_vectorize(jnp.asarray(img)))
    # vec[c + x*C + y*C*X] == img[x, y, c]
    X, C = 2, 2
    for x in range(2):
        for y in range(3):
            for c in range(2):
                assert vec[c + x * C + y * C * X] == img[x, y, c]


def test_gray_and_pixel_scalers():
    img = np.full((2, 2, 3), 255.0, np.float32)
    gray = np.asarray(GrayScaler().apply(jnp.asarray(img)))
    assert gray.shape == (2, 2, 1)
    np.testing.assert_allclose(gray, 254.99, atol=0.2)
    scaled = np.asarray(PixelScaler().apply(jnp.asarray(img)))
    np.testing.assert_allclose(scaled, 1.0)


# --- the Convolver's function standing for Convolver → rectifier → pooler


def _three(conv, rect, pool):
    """(functions, arrays) of the three nodes, as a RowwiseRun has them."""
    return tuple(zip(*(n.rowwise() for n in (conv, rect, pool))))


def _one_after_another(fns, arrays, x):
    for fn, arr in zip(fns, arrays):
        x = fn(arr, x)
    return np.asarray(x)


def _byte_images(rng, n, width, height, channels):
    return jnp.asarray(
        rng.uniform(0, 255, (n, width, height, channels)).round(), jnp.float32)


FOLD_GEOMETRIES = {
    # RandomPatchCifar's: 27 × 27 maps, windows rows 0–13 and 13–26 (row
    # 13 in both, the second cut at the edge)
    "cifar": dict(width=32, height=32, channels=3, k=6, pool=(13, 14)),
    # a stride under the pool size on a map that is not square: 12 × 8,
    # windows [0,4) [3,7) [6,10) [9,12) by [0,4) [3,7) [6,8)
    "overlap": dict(width=14, height=10, channels=2, k=3, pool=(3, 4)),
    # windows that leave rows of the map out: 11 × 11, [0,4) and [5,9)
    "gaps": dict(width=12, height=12, channels=1, k=2, pool=(5, 4)),
}


@pytest.mark.parametrize("normalize", [False, True], ids=["raw", "norm"])
@pytest.mark.parametrize("whiten", [False, True], ids=["plain", "zca"])
@pytest.mark.parametrize("geometry,filters,n,alpha,max_val,tile", [
    ("cifar", 16, 1, 0.25, 0.0, None),
    ("cifar", 144, 3, 0.25, 0.0, 128),    # a last filter tile of 16
    ("cifar", 16, 5, 0.0, 0.0, None),     # a last image tile of one
    ("overlap", 16, 3, 0.25, 0.1, None),  # max_val > 0
    ("overlap", 144, 2, 0.0, 0.0, 128),
    ("gaps", 16, 4, 0.25, 0.0, None),
], ids=["cifar-16", "cifar-144-tiles", "cifar-alpha0", "overlap-maxval",
        "overlap-144-tiles", "gaps"])
def test_folded_convolve_rectify_pool_matches_the_three_functions(
        geometry, filters, n, alpha, max_val, tile, whiten, normalize,
        monkeypatch):
    from keystone_tpu.ops.images import pallas_kernels
    from keystone_tpu.workflow.api import fold_rowwise

    if tile:
        monkeypatch.setattr(pallas_kernels, "CONV_FILTER_TILE", tile)
    g = FOLD_GEOMETRIES[geometry]
    rng = np.random.default_rng(11)
    patch = g["k"] * g["k"] * g["channels"]
    w = jnp.asarray(rng.standard_normal((filters, patch)), jnp.float32)
    whitener = None
    if whiten:
        whitener = ZCAWhitenerEstimator(eps=0.1).fit_single(
            jnp.asarray(rng.standard_normal((200, patch)), jnp.float32))
    fns, arrays = _three(
        Convolver(w, g["width"], g["height"], g["channels"],
                  whitener=whitener, normalize_patches=normalize),
        SymmetricRectifier(max_val=max_val, alpha=alpha),
        Pooler(*g["pool"]),
    )
    folded, folded_arrays = fold_rowwise(fns, arrays)
    assert [type(f).__name__ for f in folded] == ["_ConvolveRectifyPool"]
    # several images, the last a pad row of zeros
    x = _byte_images(rng, n, g["width"], g["height"], g["channels"])
    x = x.at[n - 1].set(0.0) if n > 1 else x
    want = _one_after_another(fns, arrays, x)
    got = _one_after_another(folded, folded_arrays, x)
    assert got.shape == want.shape and want.shape[-1] == 2 * filters
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("n,group", [(5, 2), (4, 2), (3, 8)])
def test_folded_function_makes_its_patches_a_group_of_images_at_a_time(
        n, group, monkeypatch):
    """More images than ``PATCH_GROUP`` go through in equal groups (five
    as three groups of two, the last padded with a zero image whose
    sums are dropped): the same rows as all at once."""
    from keystone_tpu.ops.images import core
    from keystone_tpu.workflow.api import fold_rowwise

    rng = np.random.default_rng(14)
    w = jnp.asarray(rng.standard_normal((16, 108)), jnp.float32)
    fns, arrays = fold_rowwise(*_three(
        Convolver(w, 32, 32, 3), SymmetricRectifier(alpha=0.25),
        Pooler(13, 14)))
    x = _byte_images(rng, n, 32, 32, 3)
    at_once = _one_after_another(fns, arrays, x)
    monkeypatch.setattr(core, "PATCH_GROUP", group)
    assert fns[0]._groups(n) == (-(-n // group), -(-n // -(-n // group)))
    patches, sums = jax.eval_shape(fns[0].held, arrays[0], x)
    assert patches.shape == (min(n, fns[0]._groups(n)[1]), 736, 128)
    assert sums.shape == (n, 8, 16)
    np.testing.assert_allclose(
        _one_after_another(fns, arrays, x), at_once, rtol=1e-6)


@pytest.mark.parametrize("pool", [
    Pooler(13, 14, pool_fn=lambda w: jnp.max(w, axis=(1, 2))),
    Pooler(13, 14, pixel_fn=jnp.square),
    Pooler(4, 14),   # windows more than two deep
], ids=["pool_fn", "pixel_fn", "deep"])
def test_a_pooler_with_its_own_function_takes_the_three_functions(pool):
    from keystone_tpu.workflow.api import RowwiseRun, fold_rowwise

    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.standard_normal((8, 108)), jnp.float32)
    nodes = [Convolver(w, 32, 32, 3), SymmetricRectifier(alpha=0.25), pool]
    fns, arrays = _three(*nodes)
    assert fold_rowwise(fns, arrays) == (fns, arrays)
    x = _byte_images(rng, 2, 32, 32, 3)
    want = Dataset.from_array(x)
    for node in nodes:
        want = node.apply_batch(want)
    got = RowwiseRun(nodes).apply_batch(Dataset.from_array(x))
    np.testing.assert_array_equal(
        np.asarray(got.array()), np.asarray(want.array()))


def test_fast_keeps_its_meaning_in_the_folded_function():
    """``fast`` asks the backend's default precision of the kernel's
    products as it does of the convolution's, and nothing else changes."""
    from keystone_tpu.workflow.api import fold_rowwise

    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.standard_normal((16, 108)), jnp.float32)
    x = _byte_images(rng, 2, 32, 32, 3)
    out = []
    for fast in (False, True):
        fns, arrays = fold_rowwise(*_three(
            Convolver(w, 32, 32, 3, fast=fast), SymmetricRectifier(alpha=0.25),
            Pooler(13, 14)))
        assert fns[0].conv.fast == fast
        out.append(_one_after_another(fns, arrays, x))
    # on the CPU the default precision is float32 too
    np.testing.assert_allclose(out[0], out[1], rtol=1e-5)
