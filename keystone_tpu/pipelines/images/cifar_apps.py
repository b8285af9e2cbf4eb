"""The remaining CIFAR applications: LinearPixels, RandomCifar,
RandomPatchCifarKernel, and the augmented RandomPatchCifar variants.

Reference: pipelines/images/cifar/{LinearPixels.scala:20,
RandomCifar.scala:21, RandomPatchCifarKernel.scala:20,
RandomPatchCifarAugmented.scala:33}.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from keystone_tpu.evaluation import (
    AugmentedExamplesEvaluator,
    MulticlassClassifierEvaluator,
)
from keystone_tpu.loaders.cifar import LabeledImages
from keystone_tpu.observability.tracing import span
from keystone_tpu.ops.images import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    Pooler,
    RandomImageTransformer,
    RandomPatcher,
    SymmetricRectifier,
)
from keystone_tpu.ops.learning import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
)
from keystone_tpu.ops.learning.kernel import (
    GaussianKernelGenerator,
    KernelRidgeRegression,
)
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.util.cacher import Cacher
from keystone_tpu.ops.util.nodes import ClassLabelIndicators, MaxClassifier
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.pipelines.images.random_patch_cifar import (
    RandomCifarConfig,
    build_filters,
)
from keystone_tpu.workflow.api import Pipeline

NUM_CLASSES = 10
IMAGE_SIZE = 32
NUM_CHANNELS = 3


def linear_pixels(train: LabeledImages, test: LabeledImages):
    """GrayScaler -> vectorize -> exact least squares -> argmax
    (reference: LinearPixels.scala:20)."""
    labels = ClassLabelIndicators(NUM_CLASSES)(train.labels)
    pipeline = (
        GrayScaler()
        .and_then(ImageVectorizer())
        .and_then(LinearMapEstimator(), train.images, labels)
        .and_then(MaxClassifier())
    )
    metrics = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(
        pipeline(test.images), test.labels
    )
    return pipeline, metrics


def random_cifar(
    train: LabeledImages,
    test: LabeledImages,
    num_filters: int = 100,
    patch_size: int = 6,
    pool_size: int = 14,
    pool_stride: int = 13,
    alpha: float = 0.25,
    lam: float = 10.0,
    seed: int = 0,
):
    """Random GAUSSIAN filters (no whitening) conv features
    (reference: RandomCifar.scala:21)."""
    rng = np.random.default_rng(seed)
    filters = jnp.asarray(
        rng.standard_normal(
            (num_filters, patch_size * patch_size * NUM_CHANNELS)
        ).astype(np.float32)
    )
    labels = ClassLabelIndicators(NUM_CLASSES)(train.labels)
    pipeline = (
        Convolver(
            filters, IMAGE_SIZE, IMAGE_SIZE, NUM_CHANNELS,
            normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=alpha))
        .and_then(Pooler(pool_stride, pool_size))
        .and_then(ImageVectorizer())
        .and_then(Cacher())
        .and_then(StandardScaler(), train.images)
        .and_then(Cacher())
        .and_then(LinearMapEstimator(lam=lam), train.images, labels)
        .and_then(MaxClassifier())
    )
    metrics = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(
        pipeline(test.images), test.labels
    )
    return pipeline, metrics


@dataclasses.dataclass
class RandomCifarKernelConfig(RandomCifarConfig):
    gamma: float = 2e-5
    block_size: int = 512
    num_epochs: int = 1


def random_patch_cifar_kernel(
    train: LabeledImages, test: LabeledImages, conf: RandomCifarKernelConfig
):
    """Same featurization as RandomPatchCifar, solved by kernel ridge
    regression (reference: RandomPatchCifarKernel.scala:20,55-90)."""
    filters, whitener = build_filters(train.images, conf)
    labels = ClassLabelIndicators(NUM_CLASSES)(train.labels)
    pipeline = (
        Convolver(
            filters, IMAGE_SIZE, IMAGE_SIZE, NUM_CHANNELS,
            whitener=whitener, normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size))
        .and_then(ImageVectorizer())
        .and_then(Cacher())
        .and_then(StandardScaler(), train.images)
        .and_then(
            KernelRidgeRegression(
                GaussianKernelGenerator(conf.gamma),
                conf.lam,
                conf.block_size,
                conf.num_epochs,
                block_permuter=conf.seed,
            ),
            train.images,
            labels,
        )
        .and_then(MaxClassifier())
    )
    metrics = MulticlassClassifierEvaluator(NUM_CLASSES).evaluate(
        pipeline(test.images), test.labels
    )
    return pipeline, metrics


@dataclasses.dataclass
class RandomCifarAugmentedConfig(RandomCifarConfig):
    augment_patch_size: int = 24
    augment_copies: int = 10
    # a 24 x 24 crop gives a 19 x 19 map: the 32 x 32 application's
    # Pooler(13, 14) has one window on it (range(7, 19, 13)), 2·F
    # features; 10 / 9 gives 2 x 2 windows, 8·F, the 4,096 features of
    # 512 filters that arXiv:1602.05310 states for this pipeline. The
    # Scala file could not be opened when this was set (PERF.md, PR 33)
    pool_size: int = 10
    pool_stride: int = 9


def random_patch_cifar_augmented(
    train: LabeledImages,
    test: LabeledImages,
    conf: RandomCifarAugmentedConfig,
):
    """RandomPatchCifar with random-crop train augmentation and
    center/corner test augmentation merged by the augmented evaluator
    (reference: RandomPatchCifarAugmented.scala:33)."""
    aug_size = conf.augment_patch_size
    patcher = RandomPatcher(
        conf.augment_copies, aug_size, aug_size, seed=conf.seed
    )
    aug_images = patcher.apply_batch(train.images)
    aug_labels_int = np.repeat(
        np.asarray(train.labels.array()), conf.augment_copies
    )
    aug_labels = ClassLabelIndicators(NUM_CLASSES)(
        Dataset.from_array(jnp.asarray(aug_labels_int))
    )

    filters, whitener = build_filters(aug_images, conf)
    featurizer = (
        Convolver(
            filters, aug_size, aug_size, NUM_CHANNELS,
            whitener=whitener, normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size))
        .and_then(ImageVectorizer())
        .and_then(Cacher())
    )
    pipeline = featurizer.and_then(
        StandardScaler(), aug_images
    ).and_then(
        BlockLeastSquaresEstimator(4096, num_iter=1, lam=conf.lam),
        aug_images,
        aug_labels,
    )

    test_patcher = CenterCornerPatcher(aug_size, aug_size, horizontal_flips=True)
    test_aug = test_patcher.apply_batch(test.images)
    per_image = test_patcher.patches_per_image
    names = np.repeat(np.arange(test.images.n), per_image)
    test_labels_aug = np.repeat(np.asarray(test.labels.array()), per_image)

    scores = pipeline(test_aug).get()
    metrics = AugmentedExamplesEvaluator(
        list(names), NUM_CLASSES
    ).evaluate(scores, test_labels_aug)
    return pipeline, metrics


@dataclasses.dataclass
class RandomCifarAugmentedKernelConfig(RandomCifarAugmentedConfig):
    gamma: float = 2e-4
    block_size: int = 512
    num_epochs: int = 1
    flip_chance: float = 0.5


def augment_train(train: LabeledImages, conf: RandomCifarAugmentedKernelConfig):
    """(crops, their ±1 label indicators): ``augment_copies`` random
    crops an image, each flipped with ``flip_chance``, and each source
    label repeated per crop (the reference's LabelAugmenter). Made on
    the device where the images are."""
    aug_size = conf.augment_patch_size
    patcher = RandomPatcher(
        conf.augment_copies, aug_size, aug_size, seed=conf.seed
    )
    flipper = RandomImageTransformer(
        flip_chance=conf.flip_chance, seed=conf.seed + 1
    )
    images = flipper.apply_batch(patcher.apply_batch(train.images))
    labels = ClassLabelIndicators(NUM_CLASSES)(
        Dataset.from_array(
            jnp.repeat(train.labels.array(), conf.augment_copies)
        )
    )
    return images, labels


def build_augmented_kernel_pipeline(
    train: LabeledImages, conf: RandomCifarAugmentedKernelConfig
) -> Pipeline:
    """The application's predictor, lazy: crops, flips and filters are
    made here from the training images (reference: eagerly, at
    pipeline-construction time), the featurizer, the scaler and the
    kernel solver fit at ``.fit()``. Scores come out unmerged: the
    augmented evaluator merges an image's copies."""
    with span("cifar.augment", copies=conf.augment_copies):
        aug_images, aug_labels = augment_train(train, conf)
    with span("cifar.filters", filters=conf.num_filters):
        filters, whitener = build_filters(aug_images, conf)
    aug_size = conf.augment_patch_size
    featurizer = (
        Convolver(
            filters, aug_size, aug_size, NUM_CHANNELS,
            whitener=whitener, normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size))
        .and_then(ImageVectorizer())
    )
    return featurizer.and_then(StandardScaler(), aug_images).and_then(
        KernelRidgeRegression(
            GaussianKernelGenerator(conf.gamma),
            conf.lam,
            conf.block_size,
            conf.num_epochs,
            block_permuter=conf.seed,
        ),
        aug_images,
        aug_labels,
    )


def random_patch_cifar_augmented_kernel(
    train: LabeledImages,
    test: LabeledImages,
    conf: RandomCifarAugmentedKernelConfig,
):
    """Augmented CIFAR featurization solved by Gauss-Seidel kernel ridge
    regression; train crops get an extra random horizontal flip, test
    copies are merged by the augmented evaluator (reference:
    RandomPatchCifarAugmentedKernel.scala:33-120)."""
    pipeline = build_augmented_kernel_pipeline(train, conf)
    aug_size = conf.augment_patch_size
    test_patcher = CenterCornerPatcher(
        aug_size, aug_size, horizontal_flips=True
    )
    test_aug = test_patcher.apply_batch(test.images)
    per_image = test_patcher.patches_per_image  # 10: 5 crops x flips
    names = np.repeat(np.arange(test.images.n), per_image)
    test_labels_aug = np.repeat(np.asarray(test.labels.array()), per_image)

    scores = pipeline(test_aug).get()
    metrics = AugmentedExamplesEvaluator(
        list(names), NUM_CLASSES
    ).evaluate(scores, test_labels_aug)
    return pipeline, metrics
