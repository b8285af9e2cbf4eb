"""Remaining CIFAR app tests (reference: pipelines/images/cifar/*)."""

import numpy as np
import pytest

from keystone_tpu.pipelines.images.cifar_apps import (
    RandomCifarAugmentedConfig,
    RandomCifarKernelConfig,
    linear_pixels,
    random_cifar,
    random_patch_cifar_augmented,
    random_patch_cifar_kernel,
)
from keystone_tpu.pipelines.images.random_patch_cifar import synthetic_cifar


def _spatial_cifar(n_train, n_test, seed=0):
    """Class-dependent spatial gray patterns (plain color blobs collapse
    to colliding scalars under GrayScaler, which no linear-in-gray model
    can separate 10 ways)."""
    import jax.numpy as jnp

    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.parallel.dataset import Dataset

    rng = np.random.default_rng(seed)
    x, y = np.meshgrid(np.arange(32), np.arange(32))
    patterns = [
        100 + 80 * np.sin(2 * np.pi * (x * np.cos(a) + y * np.sin(a)) / p)
        for a, p in zip(np.linspace(0, np.pi, 10, endpoint=False),
                        [4, 6, 8, 10, 12, 5, 7, 9, 11, 13])
    ]

    def make(n):
        ys = rng.integers(0, 10, n)
        imgs = np.stack(
            [patterns[c] + rng.normal(0, 10, (32, 32)) for c in ys]
        )
        imgs = np.repeat(imgs[:, :, :, None], 3, axis=3).clip(0, 255)
        return LabeledImages(
            labels=Dataset.from_array(jnp.asarray(ys.astype(np.int32))),
            images=Dataset.from_array(
                jnp.asarray(imgs.astype(np.float32))
            ),
        )

    return make(n_train), make(n_test)


def test_linear_pixels(mesh8):
    # n must exceed the 1024 gray-pixel feature dim: the exact solver has
    # no regularization (reference runs n=50000)
    train, test = _spatial_cifar(n_train=2048, n_test=64, seed=0)
    _, metrics = linear_pixels(train, test)
    assert metrics.total_accuracy > 0.8


def test_random_cifar(mesh8):
    train, test = synthetic_cifar(n_train=96, n_test=24, seed=1)
    _, metrics = random_cifar(
        train, test, num_filters=12, pool_size=14, pool_stride=13, lam=100.0
    )
    assert metrics.total_accuracy > 0.3  # better than 0.1 chance


def test_random_patch_cifar_kernel(mesh8):
    train, test = synthetic_cifar(n_train=64, n_test=16, seed=2)
    conf = RandomCifarKernelConfig(
        num_filters=8, patch_size=6, patch_steps=4,
        gamma=1e-2, block_size=32, num_epochs=3, lam=1.0,
    )
    _, metrics = random_patch_cifar_kernel(train, test, conf)
    assert metrics.total_accuracy > 0.6


def test_random_patch_cifar_augmented(mesh8):
    train, test = synthetic_cifar(n_train=48, n_test=12, seed=3)
    conf = RandomCifarAugmentedConfig(
        num_filters=8, patch_size=6, patch_steps=4, lam=50.0,
        augment_patch_size=24, augment_copies=3,
    )
    _, metrics = random_patch_cifar_augmented(train, test, conf)
    assert 0.0 <= metrics.total_accuracy <= 1.0


def test_random_patch_cifar_augmented_kernel(mesh8):
    """Augmented train crops + random flips, KRR solve, augmented-test
    merge (reference: RandomPatchCifarAugmentedKernel.scala:33)."""
    from keystone_tpu.pipelines.images.cifar_apps import (
        RandomCifarAugmentedKernelConfig,
        random_patch_cifar_augmented_kernel,
    )

    train, test = synthetic_cifar(n_train=48, n_test=12, seed=4)
    conf = RandomCifarAugmentedKernelConfig(
        num_filters=8, patch_size=6, patch_steps=4, lam=1.0,
        augment_patch_size=24, augment_copies=3,
        gamma=1e-2, block_size=48, num_epochs=2,
    )
    _, metrics = random_patch_cifar_augmented_kernel(train, test, conf)
    assert metrics.total_accuracy > 0.5  # learns on separable textures


def test_augmented_kernel_pipeline_fit_against_the_plain_reference():
    """The application on the normal path,
    build_augmented_kernel_pipeline(...).fit(), against
    benchmark/reference/cifar_augmented_krr.py (crops, flips, patch
    sample, filters and block order drawn again from the seed; im2col,
    float64 host solves; nothing of keystone_tpu) at 16 filters, 24 images
    x 5 crops, blocks of 40: the unmerged scores of the held-out images'
    ten centre-and-corner crops. Tolerance 2e-5 relative Frobenius: the
    two read 5e-7 to 1e-6 apart here."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import cifar_augmented_krr as reference
    from benchmark.reference import rel_err
    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.ops.images import CenterCornerPatcher
    from keystone_tpu.ops.learning.kernel import KernelBlockLinearMapper
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images.cifar_apps import (
        RandomCifarAugmentedKernelConfig,
        build_augmented_kernel_pipeline,
    )
    from keystone_tpu.workflow.executor import PipelineEnv

    def _seeded_images(rows, rng):
        """Smooth class-free textures in the range of uint8."""
        coarse = rng.normal(0, 1, (rows, 8, 8, 1)) + 0.3 * rng.normal(
            0, 1, (rows, 8, 8, 3))
        fine = np.kron(coarse, np.ones((1, 4, 4, 1)))
        noisy = 120 + 40 * fine + rng.normal(0, 4, fine.shape)
        return np.clip(np.round(noisy), 0, 255).astype(np.float32)

    rng = np.random.default_rng(8)
    x, xt = _seeded_images(24, rng), _seeded_images(6, rng)
    y = rng.permutation(np.arange(24) % 10).astype(np.int32)
    seed = 2147483693
    conf = RandomCifarAugmentedKernelConfig(
        num_filters=16, lam=0.1, block_size=40, seed=seed, gamma=8e-3,
        augment_copies=5,
    )
    assert (conf.pool_size, conf.pool_stride) == (10, 9)
    PipelineEnv.get_or_create().reset()
    train = LabeledImages(labels=Dataset.from_array(jnp.asarray(y)),
                          images=Dataset.from_array(jnp.asarray(x)))
    fitted = build_augmented_kernel_pipeline(train, conf).fit()
    ops = [fitted.graph.operators[n] for n in fitted._topo]
    assert ops[0].label == "Convolver+SymmetricRectifier+Pooler+ImageVectorizer"
    assert ops[0].folded  # overlapping 2 x 2 sum windows fold too
    assert isinstance(ops[-1], KernelBlockLinearMapper)
    assert ops[-1].model.shape == (120, 10)
    crops = CenterCornerPatcher(24, 24, horizontal_flips=True).apply_batch(
        Dataset.from_array(jnp.asarray(xt)))
    got = np.asarray(fitted(crops).array())
    cfg = {"image": [32, 32, 3], "augment_patch_size": 24,
           "augment_copies": 5, "flip_chance": 0.5, "patch_size": 6,
           "patch_steps": 1, "num_filters": 16, "whitening_epsilon": 0.1,
           "whitener_sample": 100000, "alpha": 0.25, "pool_size": 10,
           "pool_stride": 9, "gamma": 8e-3, "lambda": 0.1, "block_size": 40,
           "num_epochs": 1, "num_classes": 10}
    # the reference's own draws against the application's nodes
    np.testing.assert_array_equal(
        reference.heldout_crops(cfg, xt), np.asarray(crops.array()))
    a, onehot, at = reference.prepare(cfg, seed, x, y, xt)
    assert a.shape == (120, 128) and at.shape == (60, 128)
    want = reference.sweep(cfg, seed, a, onehot, at)
    assert want.shape == (60, 10) and np.std(want) > 0.05
    assert rel_err(got, want) < 2e-5
    # the one-pass control stands well clear of that tolerance
    low = reference.sweep(cfg, seed, a, onehot, at,
                          cross_precision="bfloat16")
    assert rel_err(low, want) > 1e-4
