"""SIFT / LCS / FisherVector tests.

The reference validates SIFT against a MATLAB vl_phow export
(feats128.csv) and FV against a fixture-sum constant (EncEvalSuite) — the
CSV fixtures are absent from the reference repo, so these tests validate
against independent numpy translations of the same math plus structural
invariants, and FV on a seeded GMM written and read back in the
reference's ``voc_codebook`` layout (``means.csv`` and ``variances.csv``,
dim x centers, and ``priors``) — the reference's own files are compared
in ``test_reference_fixtures.py``, where they are mounted.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.ops.images.fisher_vector import (
    FisherVector,
    GMMFisherVectorEstimator,
)
from keystone_tpu.ops.images.lcs import LCSExtractor
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
from keystone_tpu.parallel.dataset import Dataset


@pytest.fixture
def voc_codebook(tmp_path):
    """A GMM at the reference codebook's size (80-dim PCA-SIFT, 256
    centers) and descriptor scale, in its three-file layout."""
    rng = np.random.default_rng(7)
    d, k = 80, 256
    np.savetxt(tmp_path / "means.csv",
               rng.normal(0.0, 50.0, (d, k)), delimiter=",")
    np.savetxt(tmp_path / "variances.csv",
               rng.uniform(400.0, 2500.0, (d, k)), delimiter=",")
    np.savetxt(tmp_path / "priors", rng.dirichlet(np.full(k, 5.0)))
    return GaussianMixtureModel.load(
        str(tmp_path / "means.csv"),
        str(tmp_path / "variances.csv"),
        str(tmp_path / "priors"),
    )


def _test_image(h=64, w=64, seed=0):
    rng = np.random.default_rng(seed)
    x, y = np.meshgrid(np.arange(w), np.arange(h))
    img = (
        0.5
        + 0.3 * np.sin(x / 5.0)
        + 0.2 * np.cos(y / 7.0)
        + 0.05 * rng.standard_normal((h, w))
    )
    return img.astype(np.float32)


def test_sift_shapes_and_ranges():
    img = _test_image()
    ext = SIFTExtractor(step=4, bin=4, num_scales=2)
    out = np.asarray(ext.apply(img))
    assert out.shape[0] == 128
    assert out.shape[1] > 0
    assert out.min() >= 0 and out.max() <= 255
    # descriptors quantize the [0, 0.5]-ish normalized range
    assert out.max() > 0  # textured image produces energy


def test_sift_descriptor_count_matches_formula():
    img = _test_image(60, 80)
    num_scales = 2
    ext = SIFTExtractor(step=3, bin=4, num_scales=num_scales)
    out = np.asarray(ext.apply(img))
    expected = 0
    H, W = 60, 80
    for s in range(num_scales):
        b = 4 + 2 * s
        bound = (1 + 2 * num_scales) - 3 * s
        extent = 3 * b
        step_s = 3 + s  # default scale_step=1 (SIFTExtractor.scala:16)
        nfy = (H - 1 - bound - extent) // step_s + 1
        nfx = (W - 1 - bound - extent) // step_s + 1
        expected += nfy * nfx
    assert out.shape[1] == expected


def test_sift_flat_image_zeroed_by_contrast_threshold():
    img = np.full((48, 48), 0.5, np.float32)
    out = np.asarray(SIFTExtractor(step=4, bin=4, num_scales=2).apply(img))
    np.testing.assert_allclose(out, 0.0)


def test_sift_rotation_invariance_of_energy():
    """Rotating the image 90 deg permutes descriptors but preserves the
    total descriptor energy approximately (square image, symmetric
    grid)."""
    img = _test_image(64, 64)
    ext = SIFTExtractor(step=4, bin=4, num_scales=1)
    a = np.asarray(ext.apply(img))
    b = np.asarray(ext.apply(np.rot90(img).copy()))
    assert a.shape == b.shape
    assert abs(a.sum() - b.sum()) / max(a.sum(), 1) < 0.05


def test_lcs_matches_naive():
    rng = np.random.default_rng(1)
    img = rng.uniform(0, 1, (40, 40, 3)).astype(np.float32)
    s = 6
    ext = LCSExtractor(stride=4, stride_start=16, sub_patch_size=s)
    got = np.asarray(ext.apply(img))

    # naive translation of LCSExtractor.scala
    def box(c):
        pad_low = (s - 1) // 2
        pad_high = s - 1 - pad_low
        p = np.pad(img[:, :, c], ((pad_low, pad_high), (pad_low, pad_high)))
        out = np.zeros((40, 40))
        for i in range(40):
            for j in range(40):
                out[i, j] = p[i : i + s, j : j + s].mean()
        return out

    means = [box(c) for c in range(3)]
    sqs = []
    for c in range(3):
        img2 = img[:, :, c] ** 2
        pad_low = (s - 1) // 2
        pad_high = s - 1 - pad_low
        p = np.pad(img2, ((pad_low, pad_high), (pad_low, pad_high)))
        out = np.zeros((40, 40))
        for i in range(40):
            for j in range(40):
                out[i, j] = p[i : i + s, j : j + s].mean()
        sqs.append(out)
    stds = [np.sqrt(np.maximum(sqs[c] - means[c] ** 2, 0)) for c in range(3)]

    xs = list(range(16, 40 - 16, 4))
    offs = list(range(-2 * s + s // 2 - 1, s + s // 2 - 1 + 1, s))
    n_keys = len(xs) * len(xs)
    expect = np.zeros((len(offs) * len(offs) * 3 * 2, n_keys), np.float32)
    for xi, x in enumerate(xs):
        for yi, y in enumerate(xs):
            col = xi * len(xs) + yi
            idx = 0
            for c in range(3):
                for nx in offs:
                    for ny in offs:
                        expect[idx, col] = means[c][x + nx, y + ny]
                        idx += 1
                        expect[idx, col] = stds[c][x + nx, y + ny]
                        idx += 1
    np.testing.assert_allclose(got, expect, atol=1e-4)


def _np_fisher_vector(gmm_means, gmm_vars, gmm_weights, x, thresh=1e-4):
    """numpy translation of FisherVector.scala:33-52 + GMM posteriors."""
    d, m = x.shape
    mu, var, w = gmm_means.T, gmm_vars.T, gmm_weights  # (k, d)
    xs = x.T  # (m, d)
    sq = (
        (xs**2) @ (0.5 / var).T
        - xs @ (mu / var).T
        + 0.5 * (mu * mu / var).sum(1)[None, :]
    )
    llh = (
        -0.5 * d * np.log(2 * np.pi)
        - 0.5 * np.log(var).sum(1)[None, :]
        + np.log(w)[None, :]
        - sq
    )
    llh = llh - llh.max(1, keepdims=True)
    q = np.exp(llh)
    q /= q.sum(1, keepdims=True)
    q = np.where(q > thresh, q, 0.0)
    q /= q.sum(1, keepdims=True)
    s0 = q.mean(0)
    s1 = (x @ q) / m
    s2 = ((x * x) @ q) / m
    fv1 = (s1 - gmm_means * s0[None, :]) / (
        np.sqrt(gmm_vars) * np.sqrt(gmm_weights)[None, :]
    )
    fv2 = (
        s2 - 2 * gmm_means * s1 + (gmm_means**2 - gmm_vars) * s0[None, :]
    ) / (gmm_vars * np.sqrt(2 * gmm_weights)[None, :])
    return np.concatenate([fv1, fv2], axis=1)


def test_fisher_vector_matches_numpy_on_voc_codebook(voc_codebook):
    gmm = voc_codebook
    rng = np.random.default_rng(0)
    d = gmm.dim
    x = rng.standard_normal((d, 50)).astype(np.float32) * 100
    fv = FisherVector(gmm)
    got = np.asarray(fv.apply(x))
    expect = _np_fisher_vector(
        np.asarray(gmm.means, np.float64),
        np.asarray(gmm.variances, np.float64),
        np.asarray(gmm.weights, np.float64),
        x.astype(np.float64),
    )
    assert got.shape == (d, 2 * gmm.k)
    np.testing.assert_allclose(got, expect, rtol=2e-2, atol=2e-2)


def test_fisher_vector_estimator_end_to_end():
    rng = np.random.default_rng(2)
    mats = [
        rng.standard_normal((8, 30)).astype(np.float32) for _ in range(4)
    ]
    est = GMMFisherVectorEstimator(k=2, seed=0)
    fv = est.fit(Dataset.from_items(mats))
    out = fv.apply(mats[0])
    assert np.asarray(out).shape == (8, 4)


def test_fused_fisher_vector_matches_numpy_on_voc_codebook(voc_codebook):
    """Same codebook check for the fused Pallas path
    (the enceval-native parallel, external/FisherVector.scala:17)."""
    from keystone_tpu.ops.images.fisher_vector import FisherVectorFused

    gmm = voc_codebook
    rng = np.random.default_rng(0)
    d = gmm.dim
    x = rng.standard_normal((d, 50)).astype(np.float32) * 100
    got = np.asarray(FisherVectorFused(gmm).apply(x))
    expect = _np_fisher_vector(
        np.asarray(gmm.means, np.float64),
        np.asarray(gmm.variances, np.float64),
        np.asarray(gmm.weights, np.float64),
        x.astype(np.float64),
    )
    assert got.shape == (d, 2 * gmm.k)
    np.testing.assert_allclose(got, expect, rtol=2e-2, atol=2e-2)
