"""Plain reference of the RandomPatchCifarAugmentedKernel configuration
(RandomPatchCifarAugmentedKernel.scala:33-120, with RandomPatcher.scala:17,
RandomImageTransformer.scala, CenterCornerPatcher.scala:19,
KernelGenerator.scala:18-206, KernelRidgeRegression.scala:37,86-235 and
KernelBlockLinearMapper.scala:28; the featurizer's nodes as
reference/cifar_random_patch.py gives them).

Augmentation, on the host: ``augment_copies`` crops an image, each
origin two draws of ``numpy.random.default_rng(seed)`` (x, then y, crop
after crop, image after image; both uniform over the origins the image
allows); crop j is flipped along its second axis where
``default_rng(seed + 1).random(rows)[j] < flip_chance``; a crop's label
is its image's. Held-out crops: the four corners and the centre of each
held-out image, each followed by its flip, image after image.

Filters and features: reference/cifar_random_patch.py's functions on the
24 x 24 crops (the seeded patch sample of all the crops' patches in
Windower's order, ``normalizeRows``, float64 ZCA, the seeded choice of
filters; im2col, the two-sided rectifier, the pooler's windows as slices,
channel-major vectorisation; every product at ``highest``), then the
scaler's mean and standard deviation (over n - 1).

Model: the published sweep, Gauss-Seidel on the dual. The blocks of
``block_size`` consecutive rows are visited in the order
``default_rng((seed, epoch)).shuffle`` gives; per block B:
``K(:,B) = exp(-gamma max(|x|^2 + |x_B|^2 - 2 X X_B', 0))`` on the device
with the product at ``highest``, ``rhs = Y_B - K_B' W + K_BB' W_B``,
``(K_BB + lambda I) W_B = rhs`` on the host in float64 (Cholesky), W_B
written back. Scores of the held-out crops: ``sum_B K_test(:,B) W_B``. The
n x n kernel matrix is never whole: one (n, block) column block at a time.

Straightforward jax.numpy in float32 on one device. Nothing is imported
from keystone_tpu and nothing the program made is read: crops, flips,
patch sample, filters and block order are drawn again from the seed.

Departures from the Scala file, each shared with the program: the
images and labels are the benchmark's seeded ones; numpy's generators
stand for Spark's and Scala's; the block order is a seeded shuffle an
epoch; a squared distance that rounding makes negative is taken as 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import cifar_random_patch as featurizer
from benchmark.reference import rel_err
from benchmark.reference.precision import einsum_at

CROP_BLOCK = 500  # a block's maps are 361 x 512 float32 a crop: 0.74 GB


def crop_config(cfg: dict) -> dict:
    """The configuration as the featurizer's functions read it: the
    images they see are the crops."""
    size = int(cfg["augment_patch_size"])
    return dict(cfg, image=[size, size, cfg["image"][2]])


def _take(images: np.ndarray, img, x0, y0, size: int) -> np.ndarray:
    d = np.arange(size)
    return images[img[:, None, None], (x0[:, None] + d)[:, :, None],
                  (y0[:, None] + d)[:, None, :], :]


def train_crops(cfg: dict, seed: int, images: np.ndarray) -> np.ndarray:
    """(images x copies, size, size, C): random crops, randomly flipped."""
    size, copies = int(cfg["augment_patch_size"]), int(cfg["augment_copies"])
    n = images.shape[0]
    rng = np.random.default_rng(seed)
    origin = rng.integers(
        0, [images.shape[1] - size + 1, images.shape[2] - size + 1],
        size=(n * copies, 2))
    crops = _take(images, np.repeat(np.arange(n), copies),
                  origin[:, 0], origin[:, 1], size)
    flip = np.random.default_rng(seed + 1).random(n * copies) \
        < float(cfg["flip_chance"])
    crops[flip] = crops[flip][:, :, ::-1, :]
    return crops


def heldout_crops(cfg: dict, images: np.ndarray) -> np.ndarray:
    """(images x 10, size, size, C): corners and centre, each with its
    flip, image after image."""
    size = int(cfg["augment_patch_size"])
    n, far_x, far_y = images.shape[0], images.shape[1] - size, \
        images.shape[2] - size
    every = np.arange(n)
    out = []
    for x, y in [(0, 0), (far_x, 0), (0, far_y), (far_x, far_y),
                 (far_x // 2, far_y // 2)]:
        crop = _take(images, every, np.full(n, x), np.full(n, y), size)
        out += [crop, crop[:, :, ::-1, :]]
    return np.stack(out, axis=1).reshape((n * 10,) + out[0].shape[1:])


def features_of(featurize, crops: np.ndarray, dev):
    """All crops' features on the device, a block of crops at a time."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([
        featurize(jax.device_put(crops[s:s + CROP_BLOCK], dev))
        for s in range(0, crops.shape[0], CROP_BLOCK)])


def prepare(cfg: dict, seed: int, images: np.ndarray, y: np.ndarray,
            test_images: np.ndarray) -> tuple:
    """(X, Y, X_test) on the device: the standardised features of the
    training crops, their +-1 indicators, the held-out crops' features."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    crop_cfg = crop_config(cfg)
    crops = train_crops(cfg, seed, images)
    filters, means = featurizer.draw_filters(crop_cfg, seed, crops)
    featurize = featurizer.make_featurizer(
        crop_cfg, jax.device_put(filters, dev), jax.device_put(means, dev))
    phi = features_of(featurize, crops, dev)
    phi_test = features_of(featurize, heldout_crops(cfg, test_images), dev)
    n = phi.shape[0]

    @jax.jit  # jitted so that no copy of the features is made on the way
    def scaler(f):
        mean = jnp.mean(f, axis=0)
        std = jnp.sqrt(jnp.sum((f - mean) ** 2, axis=0) / (n - 1))
        return mean, jnp.where(std < 1e-12, 1.0, std)

    mean, std = scaler(phi)
    scale = jax.jit(lambda f: (f - mean) / std, donate_argnums=(0,))
    labels = np.repeat(y, int(cfg["augment_copies"]))
    onehot = 2.0 * jax.nn.one_hot(
        jax.device_put(labels, dev), int(cfg["num_classes"])) - 1.0
    return scale(phi), onehot, scale(phi_test)


def block_order(seed: int, epoch: int, blocks: int) -> list:
    order = list(range(blocks))
    np.random.default_rng((seed, epoch)).shuffle(order)
    return order


def sweep(cfg: dict, seed: int, x, onehot, x_test,
          cross_precision: str = "highest") -> np.ndarray:
    """Scores of ``x_test`` under the dual model fitted on (x, onehot);
    ``cross_precision`` is the precision of X X_B' alone."""
    import jax
    import jax.numpy as jnp
    import scipy.linalg

    gamma, lam = float(cfg["gamma"]), float(cfg["lambda"])
    b, n = int(cfg["block_size"]), x.shape[0]
    starts = list(range(0, n, b))

    @jax.jit
    def norms_of(a):
        return jnp.sum(a * a, axis=1)

    @jax.jit
    def kernel(a, a_norms, xb, xb_norms):
        cross = einsum_at("nd,bd->nb", a, xb, cross_precision)
        d2 = a_norms[:, None] + xb_norms[None, :] - 2.0 * cross
        return jnp.exp(-gamma * jnp.maximum(d2, 0.0))

    @jax.jit
    def residual(kb, w):
        return einsum_at("nb,nk->bk", kb, w)

    norms, norms_test = norms_of(x), norms_of(x_test)
    w = jnp.zeros((n, onehot.shape[1]), jnp.float32)
    for epoch in range(int(cfg["num_epochs"])):
        for i in block_order(seed, epoch, len(starts)):
            rows = slice(starts[i], min(starts[i] + b, n))
            kb = kernel(x, norms, x[rows], norms[rows])
            k_bb = np.asarray(kb[rows], np.float64)
            rhs = np.asarray(onehot[rows], np.float64) \
                - np.asarray(residual(kb, w), np.float64) \
                + k_bb.T @ np.asarray(w[rows], np.float64)
            del kb
            k_bb[np.diag_indices_from(k_bb)] += lam
            sol = scipy.linalg.cho_solve(
                scipy.linalg.cho_factor(k_bb, lower=True,
                                        overwrite_a=True), rhs)
            w = w.at[rows].set(jnp.asarray(sol, jnp.float32))
    scores = jnp.zeros((x_test.shape[0], w.shape[1]), jnp.float32)
    for s in starts:
        rows = slice(s, min(s + b, n))
        kt = kernel(x_test, norms_test, x[rows], norms[rows])
        scores = scores + einsum_at("tb,bk->tk", kt, w[rows])
    return np.asarray(scores)


def _worst(sample: dict, want: np.ndarray) -> float:
    if not sample["outputs"]:
        return float("inf")
    return max(rel_err(got, want) for got in sample["outputs"].values())


def compare(ctx, sample: dict) -> dict:
    """The worst of the kept models' held-out scores against the
    reference's, as a relative Frobenius error."""
    x, onehot, x_test = prepare(ctx.config, ctx.seed, sample["images"],
                                sample["y"], sample["test_images"])
    want = sweep(ctx.config, ctx.seed, x, onehot, x_test)
    return {"scores_rel_err": _worst(sample, want)}


def control(ctx, sample: dict) -> dict:
    """The reference with the kernel's cross term one precision step
    below the program's three bf16 passes, one pass of operands rounded
    to bf16, put in the program's place. It has to come out as not
    correct."""
    x, onehot, x_test = prepare(ctx.config, ctx.seed, sample["images"],
                                sample["y"], sample["test_images"])
    want = sweep(ctx.config, ctx.seed, x, onehot, x_test)
    low = sweep(ctx.config, ctx.seed, x, onehot, x_test,
                cross_precision="bfloat16")
    return {"bfloat16_cross_term": {"scores_rel_err": rel_err(low, want)}}
