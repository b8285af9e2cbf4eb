"""Featurize hot-loop Pallas kernels (ops/images/pallas_kernels):
kernel-vs-XLA-reference parity (the einsum formulations the kernels
replaced), backend auto-selection, and batched (bucket-vmapped) vs
per-image SIFT/LCS parity on raw uint8 input — the exact shape the
serving engine's fused bucket programs vmap over."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.images.pallas_kernels import (
    NUM_ORIENTATIONS,
    auto_interpret,
    plane_sandwich,
    sift_bin_sample,
)


@pytest.mark.parametrize(
    "backend,want", [("cpu", True), ("tpu", False)]
)
def test_auto_interpret_follows_backend(monkeypatch, backend, want):
    """interpret=None resolves from the live backend: Mosaic on tpu,
    the Pallas interpreter on cpu; explicit values pass through."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert auto_interpret() is want
    assert auto_interpret(None) is want
    assert auto_interpret(True) is True
    assert auto_interpret(False) is False


def test_auto_interpret_rejects_other_backends(monkeypatch):
    """A backend that is neither tpu nor cpu is an error, not a silent
    interpret; an explicit flag still passes through."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        auto_interpret()
    assert auto_interpret(True) is True


def test_sift_bin_sample_matches_xla_reference():
    """The fused trilinear-orientation-binning + double-GEMM kernel
    equals the one_hot-planes + einsum formulation it replaced."""
    rng = np.random.default_rng(0)
    H, W, M, N = 24, 20, 12, 8
    mag = rng.random((H, W)).astype(np.float32)
    t = (rng.random((H, W)) * NUM_ORIENTATIONS).astype(np.float32)
    ayt = rng.standard_normal((M, H)).astype(np.float32)
    ax = rng.standard_normal((W, N)).astype(np.float32)

    got = np.asarray(
        sift_bin_sample(
            jnp.asarray(mag), jnp.asarray(t), jnp.asarray(ayt),
            jnp.asarray(ax),
        )
    )
    assert got.shape == (NUM_ORIENTATIONS, M, N)

    b0 = np.floor(t).astype(np.int64) % NUM_ORIENTATIONS
    b1 = (b0 + 1) % NUM_ORIENTATIONS
    frac = t - np.floor(t)
    planes = np.zeros((NUM_ORIENTATIONS, H, W), np.float32)
    for o in range(NUM_ORIENTATIONS):
        planes[o] = mag * (
            (1.0 - frac) * (b0 == o) + frac * (b1 == o)
        )
    want = np.einsum("mh,ohw,wn->omn", ayt, planes, ax)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_plane_sandwich_matches_einsum():
    """out[p] = at @ planes[p] @ b, per plane, in one kernel."""
    rng = np.random.default_rng(1)
    P, H, W, M, N = 6, 18, 22, 9, 7
    planes = rng.standard_normal((P, H, W)).astype(np.float32)
    at = rng.standard_normal((M, H)).astype(np.float32)
    b = rng.standard_normal((W, N)).astype(np.float32)
    got = np.asarray(
        plane_sandwich(
            jnp.asarray(planes), jnp.asarray(at), jnp.asarray(b)
        )
    )
    assert got.shape == (P, M, N)
    want = np.einsum("mh,phw,wn->pmn", at, planes, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_kernels_vmap_matches_loop():
    """vmap folds a batch over the kernels exactly (the engine's
    bucket programs rely on this batching rule)."""
    rng = np.random.default_rng(2)
    B, H, W, M, N = 3, 16, 14, 6, 5
    mags = rng.random((B, H, W)).astype(np.float32)
    ts = (rng.random((B, H, W)) * NUM_ORIENTATIONS).astype(np.float32)
    ayt = jnp.asarray(rng.standard_normal((M, H)).astype(np.float32))
    ax = jnp.asarray(rng.standard_normal((W, N)).astype(np.float32))
    single = np.stack([
        np.asarray(sift_bin_sample(
            jnp.asarray(m), jnp.asarray(t), ayt, ax
        ))
        for m, t in zip(mags, ts)
    ])
    batched = np.asarray(
        jax.vmap(lambda m, t: sift_bin_sample(m, t, ayt, ax))(
            jnp.asarray(mags), jnp.asarray(ts)
        )
    )
    np.testing.assert_array_equal(batched, single)


def test_sift_batched_vmap_matches_per_image_on_uint8():
    """The bucket_vmap contract through the Pallas hot loop: a vmapped
    raw-uint8 batch yields exactly the per-image descriptor matrices
    (quantized output — any fp divergence would show as whole-step
    jumps, so equality is the honest assertion)."""
    from keystone_tpu.ops.images.sift import SIFTExtractor

    ex = SIFTExtractor(step=4, bin=4, num_scales=2)
    rng = np.random.default_rng(3)
    batch = rng.integers(0, 256, (3, 40, 40, 3), dtype=np.uint8)
    per = np.stack([np.asarray(ex.apply(img)) for img in batch])
    batched = np.asarray(jax.vmap(ex.apply)(jnp.asarray(batch)))
    np.testing.assert_array_equal(batched, per)


def test_lcs_batched_vmap_matches_per_image_on_uint8():
    from keystone_tpu.ops.images.lcs import LCSExtractor

    ex = LCSExtractor(4, 16, 6)
    rng = np.random.default_rng(4)
    batch = rng.integers(0, 256, (3, 40, 40, 3), dtype=np.uint8)
    per = np.stack([np.asarray(ex.apply(img)) for img in batch])
    batched = np.asarray(jax.vmap(ex.apply)(jnp.asarray(batch)))
    np.testing.assert_allclose(batched, per, rtol=1e-5, atol=1e-5)
