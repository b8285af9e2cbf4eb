"""End-to-end RandomPatchCifar on synthetic data (reference:
pipelines/images/cifar/RandomPatchCifar.scala)."""

import numpy as np

from keystone_tpu.pipelines.images.random_patch_cifar import (
    RandomCifarConfig,
    run,
    synthetic_cifar,
)


def test_random_patch_cifar_end_to_end(mesh8):
    train, test = synthetic_cifar(n_train=128, n_test=32, seed=0)
    conf = RandomCifarConfig(
        num_filters=16, patch_size=6, patch_steps=3, lam=10.0
    )
    _, metrics = run(train, test, conf)
    # patch normalization removes most of the synthetic color-blob signal
    # by design (contrast normalization); well above the 0.1 chance level
    # is what this featurization can give here
    assert metrics.total_accuracy > 0.6


def _seeded_images(rows, rng):
    """Smooth class-free textures in the range of uint8."""
    coarse = rng.normal(0, 1, (rows, 8, 8, 1)) + 0.3 * rng.normal(
        0, 1, (rows, 8, 8, 3))
    fine = np.kron(coarse, np.ones((1, 4, 4, 1)))
    noisy = 120 + 40 * fine + rng.normal(0, 4, fine.shape)
    return np.clip(np.round(noisy), 0, 255).astype(np.float32)


def test_fit_through_build_pipeline_against_the_plain_reference(mesh8):
    """The application on the normal path, build_pipeline(...).fit(),
    against benchmark/reference/cifar_random_patch.py (im2col, explicit
    normalisation, float64 host solves; nothing of keystone_tpu) at 32
    filters, 192 images, blocks of 64: the scores before MaxClassifier.
    Tolerance 2e-5 relative Frobenius: float32 products at highest on
    both sides read 1.4e-6 to 1.7e-6 here, and the reference with its
    products at three bf16 passes reads 6e-6."""
    import jax.numpy as jnp

    from benchmark.reference import cifar_random_patch as reference
    from benchmark.reference import rel_err
    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.ops.util.nodes import MaxClassifier
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images.random_patch_cifar import (
        build_pipeline,
    )

    rng = np.random.default_rng(5)
    x, xt = _seeded_images(192, rng), _seeded_images(40, rng)
    y = rng.permutation(np.arange(192) % 10).astype(np.int32)
    conf = RandomCifarConfig(num_filters=32, lam=30.0, block_size=64, seed=9)
    train = LabeledImages(labels=Dataset.from_array(jnp.asarray(y)),
                          images=Dataset.from_array(jnp.asarray(x)))
    fitted = build_pipeline(train, conf).fit()
    ops = [fitted.graph.operators[n] for n in fitted._topo]
    assert isinstance(ops[-1], MaxClassifier)
    assert ops[0].label == "Convolver+SymmetricRectifier+Pooler+ImageVectorizer"
    scores = Dataset.from_array(jnp.asarray(xt))
    for op in ops[:-1]:
        scores = op.batch_transform([scores])
    cfg = {"image": [32, 32, 3], "patch_size": 6, "patch_steps": 1,
           "num_filters": 32, "whitening_epsilon": 0.1,
           "whitener_sample": 100000, "alpha": 0.25, "pool_size": 14,
           "pool_stride": 13, "block_size": 64, "lambda": 30.0,
           "num_classes": 10}
    want = reference.fit_and_score(cfg, 9, x, y, xt)
    assert want.shape == (40, 10) and np.std(want) > 0.05
    assert rel_err(np.asarray(scores.array()), want) < 2e-5


def test_folded_fit_scores_as_the_unfolded_nodes_do(mesh8, monkeypatch):
    """build_pipeline(...).fit() with the Convolver's function standing
    for Convolver → rectifier → pooler, against the same fit with the
    three functions left as they are (nothing absorbed): the held-out
    scores before MaxClassifier agree to 1e-5, and so do the fitted
    pipeline's scores through its own nodes one by one."""
    import jax.numpy as jnp

    from benchmark.reference import rel_err
    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.ops.images import core
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images.random_patch_cifar import (
        build_pipeline,
    )
    from keystone_tpu.workflow.api import RowwiseRun
    from keystone_tpu.workflow.executor import PipelineEnv

    rng = np.random.default_rng(6)
    x, xt = _seeded_images(192, rng), _seeded_images(40, rng)
    y = rng.permutation(np.arange(192) % 10).astype(np.int32)
    conf = RandomCifarConfig(num_filters=32, lam=30.0, block_size=64, seed=9)

    def scores(node_by_node=False):
        PipelineEnv.get_or_create().reset()
        train = LabeledImages(labels=Dataset.from_array(jnp.asarray(y)),
                              images=Dataset.from_array(jnp.asarray(x)))
        fitted = build_pipeline(train, conf).fit()
        ops = [fitted.graph.operators[n] for n in fitted._topo]
        assert isinstance(ops[0], RowwiseRun)
        out = Dataset.from_array(jnp.asarray(xt))
        for op in ops[:-1]:
            if node_by_node and isinstance(op, RowwiseRun):
                out = op._node_by_node(out)
            else:
                out = op.batch_transform([out])
        return ops[0].folded, np.asarray(out.array())

    folded, got = scores()
    assert folded and got.shape == (40, 10) and np.std(got) > 0.05
    _, through_nodes = scores(node_by_node=True)
    assert rel_err(got, through_nodes) < 1e-5
    monkeypatch.setattr(core._Convolve, "absorb", lambda self, rest: None)
    folded, want = scores()
    assert not folded
    assert rel_err(got, want) < 1e-5


def test_sample_patches_is_the_sampler_over_the_windower():
    """build_filters gathers the sampled patches alone: the same rows as
    Sampler over ImageVectorizer over Windower, which makes them all."""
    import jax.numpy as jnp

    from keystone_tpu.ops.images import ImageVectorizer, Windower
    from keystone_tpu.ops.stats import Sampler
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    rng = np.random.default_rng(2)
    images = Dataset.from_array(jnp.asarray(_seeded_images(12, rng)))
    for steps, size in ((1, 6), (3, 5)):
        conf = RandomCifarConfig(patch_size=size, patch_steps=steps, seed=4)
        monkey = app.WHITENER_SAMPLE
        app.WHITENER_SAMPLE = 500
        try:
            got = np.asarray(app.sample_patches(images, conf))
        finally:
            app.WHITENER_SAMPLE = monkey
        vecs = ImageVectorizer().apply_batch(Windower(steps, size).apply(images))
        want = np.asarray(Sampler(500, seed=4).apply(vecs).array())
        np.testing.assert_array_equal(got, want)
