"""RandomPatchCifar — random-patch convolutional features + ZCA + pooling
+ block least squares.

Reference: pipelines/images/cifar/RandomPatchCifar.scala:21 — sample random
patches via Windower, normalize + ZCA-whiten them into a filter bank
(computed eagerly at pipeline-construction time, :45-57), then
Convolver -> SymmetricRectifier -> sum Pooler -> vectorize ->
StandardScaler -> BlockLeastSquaresEstimator(4096, 1, λ) -> argmax.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.cifar import CifarLoader, LabeledImages
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.images import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.ops.learning import (
    BlockLeastSquaresEstimator,
    ZCAWhitenerEstimator,
)
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.util.nodes import ClassLabelIndicators, MaxClassifier
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Pipeline

NUM_CLASSES = 10
IMAGE_SIZE = 32
NUM_CHANNELS = 3
WHITENER_SAMPLE = 100_000


@dataclasses.dataclass
class RandomCifarConfig:
    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    whitening_epsilon: float = 0.1
    patch_size: int = 6
    patch_steps: int = 1
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    lam: float = 0.0
    block_size: int = 4096  # the solver's; RandomPatchCifar.scala:21 fixes it
    seed: int = 0


def _normalize_rows(mat: np.ndarray, alpha: float) -> np.ndarray:
    """Stats.normalizeRows (reference: utils/Stats.scala:112-123). The
    centred matrix is made once and scaled in place: on 100,000 patches
    this is host time every fit, with the chip idle under it."""
    means = np.nan_to_num(mat.mean(axis=1))
    centred = mat - means[:, None]
    var = np.einsum("ij,ij->i", centred, centred) / (mat.shape[1] - 1)
    sds = np.sqrt(var + alpha)
    sds = np.where(np.isnan(sds), np.sqrt(alpha), sds)
    centred /= sds[:, None]
    return centred


@partial(jax.jit, static_argnames=("size",))
def _gather_patches(imgs, img, x0, y0, *, size: int):
    """Patch j = imgs[img_j, x0_j : x0_j + size, y0_j : y0_j + size, :],
    vectorised channel-major. One gather of whole windows: indexed value
    by value (36 index pairs a patch) it took the TPU compiler six
    minutes (PERF.md, PR 31)."""
    n, X, Y, C = imgs.shape
    rows = imgs.reshape(n, X, Y * C)  # a window's y and c are contiguous
    patches = jax.vmap(
        lambda i, x, y: jax.lax.dynamic_slice(
            rows, (i, x, y * C), (1, size, size * C)
        )[0]
    )(img, x0, y0).reshape(-1, size, size, C)
    return jnp.transpose(patches, (0, 2, 1, 3)).reshape(img.shape[0], -1)


def sample_patches(train_images: Dataset, conf: RandomCifarConfig):
    """The rows that ``Sampler(WHITENER_SAMPLE, seed)`` draws of
    ``ImageVectorizer`` over ``Windower(patch_steps, patch_size)``, made
    without the others: the same draw over the same order (image, then
    x, then y), gathered from the images where they are. All 9.1 M
    patches of one chip's 12,544 images, 729 slices stacked, do not fit
    the chip (PERF.md, PR 31); the sample is 43 MB."""
    ds = Dataset.of(train_images).to_array_mode()
    imgs = ds.padded()
    k = conf.patch_size
    xs = np.arange(0, imgs.shape[1] - k + 1, conf.patch_steps)
    ys = np.arange(0, imgs.shape[2] - k + 1, conf.patch_steps)
    per_image = len(xs) * len(ys)
    total = ds.n * per_image
    rng = np.random.default_rng(conf.seed)
    idx = np.sort(
        rng.choice(total, size=min(WHITENER_SAMPLE, total), replace=False)
    )
    img, pos = idx // per_image, idx % per_image
    return _gather_patches(
        imgs, jnp.asarray(img, jnp.int32),
        jnp.asarray(xs[pos // len(ys)], jnp.int32),
        jnp.asarray(ys[pos % len(ys)], jnp.int32), size=k,
    )


def build_filters(train_images: Dataset, conf: RandomCifarConfig):
    """Sample patches, normalize, fit ZCA, emit whitened filter bank
    (reference: RandomPatchCifar.scala:45-57)."""
    sample = sample_patches(train_images, conf)
    base = _normalize_rows(np.asarray(sample, np.float64), 10.0)
    whitener = ZCAWhitenerEstimator(eps=conf.whitening_epsilon).fit_single(
        jnp.asarray(base, jnp.float32)
    )
    rng = np.random.default_rng(conf.seed)
    idx = rng.choice(
        base.shape[0], size=min(conf.num_filters, base.shape[0]),
        replace=False,
    )
    unnorm = np.asarray(whitener.apply(jnp.asarray(base[idx], jnp.float32)))
    norms = np.sqrt((unnorm**2).sum(axis=1))
    filters = (unnorm / (norms[:, None] + 1e-10)) @ np.asarray(
        whitener.whitener
    ).T
    return jnp.asarray(filters, jnp.float32), whitener


def build_pipeline(
    train: LabeledImages, conf: RandomCifarConfig
) -> Pipeline:
    with span("cifar.filters", filters=conf.num_filters):
        filters, whitener = build_filters(train.images, conf)
    labels = ClassLabelIndicators(NUM_CLASSES)(train.labels)
    featurizer = (
        Convolver(
            filters, IMAGE_SIZE, IMAGE_SIZE, NUM_CHANNELS,
            whitener=whitener, normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size))
        .and_then(ImageVectorizer())
    )
    return (
        featurizer.and_then(StandardScaler(), train.images)
        .and_then(
            BlockLeastSquaresEstimator(
                conf.block_size, num_iter=1, lam=conf.lam
            ),
            train.images,
            labels,
        )
        .and_then(MaxClassifier())
    )


def run(train: LabeledImages, test: LabeledImages, conf: RandomCifarConfig):
    pipeline = build_pipeline(train, conf)
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    metrics = evaluator.evaluate(pipeline(test.images), test.labels)
    return pipeline, metrics


def synthetic_cifar(n_train=256, n_test=64, seed=0):
    """Class-dependent color blobs standing in for CIFAR."""
    rng = np.random.default_rng(seed)
    means = rng.uniform(30, 220, size=(NUM_CLASSES, NUM_CHANNELS))

    def make(n):
        y = rng.integers(0, NUM_CLASSES, n)
        imgs = (
            means[y][:, None, None, :]
            + rng.normal(0, 20, (n, IMAGE_SIZE, IMAGE_SIZE, NUM_CHANNELS))
        ).clip(0, 255)
        return LabeledImages(
            labels=Dataset.from_array(jnp.asarray(y.astype(np.int32))),
            images=Dataset.from_array(jnp.asarray(imgs.astype(np.float32))),
        )

    return make(n_train), make(n_test)


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description="RandomPatchCifar")
    p.add_argument("--trainLocation", default="")
    p.add_argument("--testLocation", default="")
    p.add_argument("--numFilters", type=int, default=100)
    p.add_argument("--whiteningEpsilon", type=float, default=0.1)
    p.add_argument("--patchSize", type=int, default=6)
    p.add_argument("--patchSteps", type=int, default=1)
    p.add_argument("--poolSize", type=int, default=14)
    p.add_argument("--poolStride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lambda", dest="lam", type=float, default=0.0)
    a = p.parse_args(argv)
    conf = RandomCifarConfig(
        a.trainLocation, a.testLocation, a.numFilters, a.whiteningEpsilon,
        a.patchSize, a.patchSteps, a.poolSize, a.poolStride, a.alpha, a.lam,
    )
    if conf.train_location:
        train = CifarLoader(conf.train_location)
        test = CifarLoader(conf.test_location)
    else:
        train, test = synthetic_cifar()
    t0 = time.time()
    _, metrics = run(train, test, conf)
    print(metrics.summary())
    print(f"Total time: {time.time() - t0:.2f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
