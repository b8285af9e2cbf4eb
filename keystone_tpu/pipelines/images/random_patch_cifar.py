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
    ZCAWhitener,
    ZCAWhitenerEstimator,
)
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.util.nodes import ClassLabelIndicators, MaxClassifier
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
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


def _normalize_rows(mat, alpha: float):
    """Stats.normalizeRows (reference: utils/Stats.scala:112-123), on
    the device in the sample's own float32."""
    means = jnp.nan_to_num(jnp.mean(mat, axis=1))
    centred = mat - means[:, None]
    var = jnp.sum(centred * centred, axis=1) / (mat.shape[1] - 1)
    sds = jnp.sqrt(var + alpha)
    sds = jnp.where(jnp.isnan(sds), jnp.sqrt(alpha), sds)
    return centred / sds[:, None]


# Windows gathered a step of the gather's scan: five steps for the
# sample of 100,000, where a step an index was a loop of 100,000 on the
# chip (0.24 s a fit for 0.014 now; whole, 0.012: PERF.md, PR 34). A
# step's whole-image rows are GATHER_SLAB x X x Y*C floats (246 MB at
# 32 x 32 x 3) on any backend, a CPU under the tests among them.
GATHER_SLAB = 20_000


def _windows(rows, img, x0, y0, size: int, channels: int):
    """Window j = rows[img_j, x0_j : x0_j + size, y0_j*C : (y0_j + size)*C]
    of ``rows`` (n, X, Y*C), with no loop over j: one gather of whole
    images, then one one-hot product an axis, which at ``highest`` is
    exact (every output is one input times 1.0, plus zeros)."""
    _, X, W = rows.shape
    whole = jnp.take(rows, img, axis=0)  # (m, X, Y*C)
    at_x = (
        x0[:, None, None] + jnp.arange(size)[None, :, None]
        == jnp.arange(X)[None, None, :]
    ).astype(rows.dtype)  # (m, size, X)
    strips = jnp.einsum(
        "jax,jxw->jaw", at_x, whole, precision=jax.lax.Precision.HIGHEST
    )
    at_y = (
        y0[:, None, None] * channels
        + jnp.arange(size * channels)[None, None, :]
        == jnp.arange(W)[None, :, None]
    ).astype(rows.dtype)  # (m, Y*C, size*C)
    return jnp.einsum(
        "jaw,jwb->jab", strips, at_y, precision=jax.lax.Precision.HIGHEST
    )


@partial(jax.jit, static_argnames=("size",))
def _gather_windows(imgs, img, x0, y0, *, size: int):
    """Patch j = imgs[img_j, x0_j : x0_j + size, y0_j : y0_j + size, :],
    vectorised channel-major, GATHER_SLAB patches a scan step. The
    images' rows are laid out once, outside the scan (inside it the TPU
    compiler copied all of them every step)."""
    n, X, Y, C = imgs.shape
    rows = imgs.reshape(n, X, Y * C)  # a window's y and c are contiguous
    m = img.shape[0]
    slab = min(GATHER_SLAB, m)

    def slabs(index):  # (steps, slab), the last step padded with index 0
        return jnp.pad(index, (0, -m % slab)).reshape(-1, slab)

    patches = jax.lax.map(
        lambda at: _windows(rows, *at, size, C),
        (slabs(img), slabs(x0), slabs(y0)),
    ).reshape(-1, size, size, C)[:m]
    return jnp.transpose(patches, (0, 2, 1, 3)).reshape(m, -1)


def _draw_windows(ds: Dataset, conf: RandomCifarConfig):
    """``Sampler(WHITENER_SAMPLE, seed)``'s draw over the windows in
    ``Windower``'s order (image, then x, then y), on the host as the
    configuration states it: image, x and y of each sampled window."""
    k = conf.patch_size
    _, X, Y, _ = ds.padded().shape
    xs = np.arange(0, X - k + 1, conf.patch_steps)
    ys = np.arange(0, Y - k + 1, conf.patch_steps)
    per_image = len(xs) * len(ys)
    total = ds.n * per_image
    rng = np.random.default_rng(conf.seed)
    idx = np.sort(
        rng.choice(total, size=min(WHITENER_SAMPLE, total), replace=False)
    )
    img, pos = idx // per_image, idx % per_image
    return (
        img.astype(np.int32),
        xs[pos // len(ys)].astype(np.int32),
        ys[pos % len(ys)].astype(np.int32),
    )


def sample_patches(train_images: Dataset, conf: RandomCifarConfig):
    """The rows that ``Sampler(WHITENER_SAMPLE, seed)`` draws of
    ``ImageVectorizer`` over ``Windower(patch_steps, patch_size)``, made
    without the others: the same draw over the same order (image, then
    x, then y), gathered from the images where they are. All 9.1 M
    patches of one chip's 12,544 images, 729 slices stacked, do not fit
    the chip (PERF.md, PR 31); the sample is 43 MB."""
    ds = Dataset.of(train_images).to_array_mode()
    return _gather_windows(
        ds.padded(), *jax.device_put(_draw_windows(ds, conf)),
        size=conf.patch_size,
    )


@partial(jax.jit, static_argnames=("size",))
def _filter_bank(imgs, img, x0, y0, pick, eps, *, size: int):
    """Everything of ``build_filters`` after the host's two draws, as
    one program: the sampled patches, their rows normalised, the ZCA
    fitted on them (``ZCAWhitenerEstimator.fit_single``'s arithmetic,
    traced), the picked rows whitened, scaled to unit norm and taken
    back through the whitener's transpose."""
    base = _normalize_rows(_gather_windows(imgs, img, x0, y0, size=size), 10.0)
    whitener = ZCAWhitenerEstimator(eps=eps).fit_single(base)
    unnorm = whitener.apply(jnp.take(base, pick, axis=0))
    norms = jnp.sqrt(jnp.sum(unnorm * unnorm, axis=1))
    filters = mm(unnorm / (norms[:, None] + 1e-10), whitener.whitener.T)
    return filters, whitener.whitener, whitener.means


def build_filters(train_images: Dataset, conf: RandomCifarConfig):
    """Sample patches, normalize, fit ZCA, emit whitened filter bank
    (reference: RandomPatchCifar.scala:45-57). The host draws the
    sample's windows and the filters' rows, every call, by the
    configuration's rule; from their one upload to the bank nothing
    leaves the device."""
    ds = Dataset.of(train_images).to_array_mode()
    windows = _draw_windows(ds, conf)
    sampled = windows[0].shape[0]
    pick = np.random.default_rng(conf.seed).choice(
        sampled, size=min(conf.num_filters, sampled), replace=False
    ).astype(np.int32)
    filters, whitener, means = _filter_bank(
        ds.padded(), *jax.device_put((*windows, pick)),
        conf.whitening_epsilon, size=conf.patch_size,
    )
    return filters, ZCAWhitener(whitener, means)


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
