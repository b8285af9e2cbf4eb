"""Plain reference of the RandomPatchCifar configuration
(RandomPatchCifar.scala:21,45-57, with Convolver.scala:128-205,
SymmetricRectifier.scala:7, Pooler.scala:21, ImageVectorizer.scala,
StandardScaler.scala:38 and BlockLinearMapper.scala:199-283).

Filters: every 6x6x3 patch of the training images in Windower's order
(image, then x, then y), vectorised channel-major (c + x C + y C k); the
seeded sample of ``whitener_sample`` of them; ``Stats.normalizeRows`` (mean,
variance over P - 1, + 10, square root); ZCA (means, covariance over n - 1,
V diag(1 / sqrt(lambda + eps)) V'); ``num_filters`` of the sample's rows,
whitened, scaled to unit norm, times the whitener's transpose.

Features, in blocks of images so that they fit: patches by explicit im2col
``(rows, 729, 108)``, normalised the same way, the whitener's means
subtracted, times the filters' transpose; the two-sided rectifier; the
pooler's four windows as slices, summed; channel-major vectorisation.

Model: the scaler's mean and standard deviation (over n - 1); one
Gauss-Seidel sweep of mean-centred block least squares over blocks of
``block_size`` columns, each Gram on the device and each (b, b) system on
the host in float64; scores = (phi - mu) W + mean(Y).

Straightforward jax.numpy in float32 with every product at ``highest``
(reference/precision.py) on one device. Nothing is imported from
keystone_tpu and nothing the program made is read: the patch sample and
the choice of filters are drawn again from the seed by the configuration's
rule (``numpy.random.default_rng(seed)``, once for each).

Departures from RandomPatchCifar.scala, each shared with the program:
the images, labels and held-out images are the benchmark's seeded ones
(the configuration's ``assumed``); the patch sample is ``choice`` without
replacement where Spark's ``takeSample`` draws its own; the filter matrix
is small enough that the normalisation and the ZCA are done in float64 on
the host here (the program does the ZCA in float32 on the device).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import rel_err
from benchmark.reference.precision import einsum_at

IMAGE_BLOCK = 64
VAR_CONSTANT = 10.0


def sizes(cfg: dict) -> tuple:
    """(image side, channels, patch side, positions along an axis)."""
    side, _, channels = cfg["image"]
    k = int(cfg["patch_size"])
    return int(side), int(channels), k, int(side) - k + 1


def normalize_rows(mat, alpha: float):
    """Stats.normalizeRows, numpy or jax.numpy alike."""
    mean = mat.mean(axis=-1, keepdims=True)
    var = ((mat - mean) ** 2).sum(axis=-1, keepdims=True) \
        / (mat.shape[-1] - 1)
    return (mat - mean) / (var + alpha) ** 0.5


def draw_filters(cfg: dict, seed: int, images: np.ndarray) -> tuple:
    """(filters (F, P), the whitener's means (P,)), float32."""
    side, channels, k, res = sizes(cfg)
    per_image = res * res
    total = images.shape[0] * per_image
    rng = np.random.default_rng(seed)
    take = min(int(cfg["whitener_sample"]), total)
    idx = np.sort(rng.choice(total, size=take, replace=False))
    img, pos = idx // per_image, idx % per_image
    x0, y0 = pos // res, pos % res
    dx = np.arange(k)
    # patch[j, x, y, c] = image[img_j, x0_j + x, y0_j + y, c]
    patches = images[
        img[:, None, None], (x0[:, None] + dx)[:, :, None],
        (y0[:, None] + dx)[:, None, :], :]
    vecs = patches.transpose(0, 2, 1, 3).reshape(take, -1)
    base = normalize_rows(vecs.astype(np.float64), VAR_CONSTANT)
    means = base.mean(axis=0)
    centred = base - means
    cov = centred.T @ centred / (take - 1.0)
    lam, vec = np.linalg.eigh(cov)
    scale = 1.0 / np.sqrt(np.maximum(lam, 0.0)
                          + float(cfg["whitening_epsilon"]))
    whitener = (vec * scale) @ vec.T
    rng = np.random.default_rng(seed)
    pick = rng.choice(take, size=min(int(cfg["num_filters"]), take),
                      replace=False)
    unnorm = (base[pick] - means) @ whitener
    norms = np.sqrt((unnorm ** 2).sum(axis=1))
    filters = (unnorm / (norms[:, None] + 1e-10)) @ whitener.T
    return filters.astype(np.float32), means.astype(np.float32)


def make_featurizer(cfg: dict, filters, means, precision: str = "highest"):
    """images (rows, X, Y, C) -> features (rows, 2 x 2 x 2F), jitted."""
    import jax
    import jax.numpy as jnp

    side, channels, k, res = sizes(cfg)
    alpha = float(cfg["alpha"])
    half = int(cfg["pool_size"]) // 2
    centres = list(range(half, res, int(cfg["pool_stride"])))

    def window(c):  # truncated at the map's edge
        return slice(c - half, min(c + half, res))

    @jax.jit
    def featurize(images):
        x = images.astype(jnp.float32)
        # im2col: column c + dx C + dy C k of position (px, py)
        cols = [x[:, dx:dx + res, dy:dy + res, :]
                for dy in range(k) for dx in range(k)]
        patches = jnp.concatenate(cols, axis=-1).reshape(
            x.shape[0], res * res, k * k * channels)
        normed = normalize_rows(patches, VAR_CONSTANT) - means
        maps = einsum_at("rpj,fj->rpf", normed, filters, precision)
        maps = maps.reshape(x.shape[0], res, res, -1)
        both = jnp.concatenate(
            [jnp.maximum(0.0, maps - alpha),
             jnp.maximum(0.0, -maps - alpha)], axis=-1)
        pooled = jnp.stack([
            jnp.stack([both[:, window(cx), window(cy), :].sum(axis=(1, 2))
                       for cy in centres], axis=1)
            for cx in centres], axis=1)
        return pooled.transpose(0, 2, 1, 3).reshape(x.shape[0], -1)

    return featurize


def features_of(featurize, images: np.ndarray, dev, low=None):
    """All rows' features on the device, a block of images at a time."""
    import jax
    import jax.numpy as jnp

    out = []
    for s in range(0, images.shape[0], IMAGE_BLOCK):
        f = featurize(jax.device_put(images[s:s + IMAGE_BLOCK], dev))
        out.append(f if low is None else f.astype(low).astype(jnp.float32))
    return jnp.concatenate(out)


def fit_and_score(cfg: dict, seed: int, images: np.ndarray, y: np.ndarray,
                  test_images: np.ndarray, precision: str = "highest",
                  feature_dtype=None) -> np.ndarray:
    """Scores of ``test_images`` under the model fitted on (images, y);
    ``feature_dtype`` rounds the features to a lower precision."""
    import jax
    import jax.numpy as jnp
    import scipy.linalg

    dev = jax.devices()[0]
    k = int(cfg["num_classes"])
    block, lam = int(cfg["block_size"]), float(cfg["lambda"])
    filters, means = draw_filters(cfg, seed, images)
    featurize = make_featurizer(
        cfg, jax.device_put(filters, dev), jax.device_put(means, dev),
        precision)
    phi = features_of(featurize, images, dev, feature_dtype)
    phi_test = features_of(featurize, test_images, dev, feature_dtype)
    n = phi.shape[0]

    def mm(spec, a, b):
        return einsum_at(spec, a, b, precision)

    @jax.jit  # jitted so that no copy of the features is made on the way
    def scaler(f):
        mean = jnp.mean(f, axis=0)
        std = jnp.sqrt(jnp.sum((f - mean) ** 2, axis=0) / (n - 1))
        return mean, jnp.where(std < 1e-12, 1.0, std)

    mean, std = scaler(phi)
    scale = jax.jit(lambda f: (f - mean) / std)
    phi, phi_test = scale(phi), scale(phi_test)
    onehot = 2.0 * jax.nn.one_hot(jax.device_put(y, dev), k) - 1.0
    mu_y = jnp.mean(onehot, axis=0)
    resid = onehot - mu_y
    scores = jnp.zeros((phi_test.shape[0], k), jnp.float32) + mu_y
    for s in range(0, phi.shape[1], block):
        a = phi[:, s:s + block]
        mu = jnp.mean(a, axis=0)
        a = a - mu
        g64 = np.asarray(mm("nb,nc->bc", a, a), np.float64)
        g64 += lam * np.eye(g64.shape[0])
        rhs = np.asarray(mm("nb,nk->bk", a, resid), np.float64)
        sol = scipy.linalg.cho_solve(
            scipy.linalg.cho_factor(g64, lower=True), rhs)
        w = jax.device_put(sol.astype(np.float32), dev)
        resid = resid - mm("nb,bk->nk", a, w)
        scores = scores + mm("nb,bk->nk", phi_test[:, s:s + block] - mu, w)
    return np.asarray(scores)


def _worst(sample: dict, want: np.ndarray) -> float:
    if not sample["outputs"]:
        return float("inf")
    return max(rel_err(got, want) for got in sample["outputs"].values())


def compare(ctx, sample: dict) -> dict:
    """The worst of the kept models' held-out scores against the
    reference's, as a relative Frobenius error."""
    want = fit_and_score(ctx.config, ctx.seed, sample["images"], sample["y"],
                         sample["test_images"])
    return {"scores_rel_err": _worst(sample, want)}


def control(ctx, sample: dict) -> dict:
    """The reference one precision step down, put in the program's
    place, two ways: every product at ``high`` (three bf16 passes, for a
    configuration that states float32 at ``highest``), and the features
    rounded to bfloat16. Each has to come out as not correct."""
    import jax.numpy as jnp

    args = (ctx.config, ctx.seed, sample["images"], sample["y"],
            sample["test_images"])
    want = fit_and_score(*args)
    return {
        "high": {"scores_rel_err": rel_err(
            fit_and_score(*args, precision="high"), want)},
        "bfloat16_features": {"scores_rel_err": rel_err(
            fit_and_score(*args, feature_dtype=jnp.bfloat16), want)},
    }
