"""End-to-end RandomPatchCifar on synthetic data (reference:
pipelines/images/cifar/RandomPatchCifar.scala)."""

import numpy as np
import pytest

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


def _host_filter_bank(images, conf, sample_size):
    """The path ``build_filters`` took until PR 34, stated in float64
    numpy: the sample's windows by fancy indexing, ``normalizeRows``,
    the ZCA by SVD, the drawn rows whitened, scaled and taken back
    through the whitener's transpose. Returns (sample, filters,
    whitener, means)."""
    k, steps = conf.patch_size, conf.patch_steps
    n, X, Y, _ = images.shape
    xs, ys = np.arange(0, X - k + 1, steps), np.arange(0, Y - k + 1, steps)
    per_image = len(xs) * len(ys)
    total = n * per_image
    idx = np.sort(np.random.default_rng(conf.seed).choice(
        total, size=min(sample_size, total), replace=False))
    img, pos = idx // per_image, idx % per_image
    sample = np.stack([
        images[i, x:x + k, y:y + k, :].transpose(1, 0, 2).ravel()
        for i, x, y in zip(img, xs[pos // len(ys)], ys[pos % len(ys)])
    ])
    mat = sample.astype(np.float64)
    centred = mat - mat.mean(axis=1)[:, None]
    var = (centred ** 2).sum(axis=1) / (mat.shape[1] - 1)
    base = centred / np.sqrt(var + 10.0)[:, None]
    means = base.mean(axis=0)
    _, s, vt = np.linalg.svd(base - means, full_matrices=False)
    scale = 1.0 / np.sqrt(s * s / (len(base) - 1.0) + conf.whitening_epsilon)
    whitener = (vt.T * scale) @ vt
    pick = np.random.default_rng(conf.seed).choice(
        len(base), size=min(conf.num_filters, len(base)), replace=False)
    unnorm = (base[pick] - means) @ whitener
    norms = np.sqrt((unnorm ** 2).sum(axis=1))
    filters = (unnorm / (norms[:, None] + 1e-10)) @ whitener.T
    return sample, filters, whitener, means


@pytest.mark.parametrize(
    "side,steps,rows,sample_size,num_filters",
    [
        (32, 1, 6, 600, 16),  # RandomPatchCifar's geometry
        (24, 1, 8, 600, 16),  # the augmented applications' crops
        (32, 2, 6, 500, 40),
        (24, 2, 6, 300, 700),  # more filters asked than sampled: all rows
        (24, 3, 3, 600, 147),  # 147 windows in all: the sample is all
    ],
)
def test_filter_bank_against_the_float64_host_path(
        monkeypatch, side, steps, rows, sample_size, num_filters):
    """One device program against the host path it replaced (float64
    numpy, kept above): bank and whitener to 1e-5, relative Frobenius,
    and as many filters as ``min(num_filters, sample)``."""
    import jax.numpy as jnp

    from benchmark.reference import rel_err
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    rng = np.random.default_rng(side + steps)
    images = _seeded_images(rows, rng)[:, :side, :side, :]
    conf = RandomCifarConfig(num_filters=num_filters, patch_steps=steps, seed=11)
    monkeypatch.setattr(app, "WHITENER_SAMPLE", sample_size)
    filters, whitener = app.build_filters(
        Dataset.from_array(jnp.asarray(images)), conf)
    sample, want, want_whitener, want_means = _host_filter_bank(
        images, conf, sample_size)
    assert filters.shape == (min(num_filters, len(sample)), 108)
    assert filters.dtype == jnp.float32
    assert rel_err(np.asarray(filters), want) < 1e-5
    assert rel_err(np.asarray(whitener.whitener), want_whitener) < 1e-5
    assert np.abs(np.asarray(whitener.means) - want_means).max() < 1e-6
    got = np.asarray(app.sample_patches(
        Dataset.from_array(jnp.asarray(images)), conf))
    np.testing.assert_array_equal(got, sample)


@pytest.mark.parametrize("slab", [50, 10_000])
def test_window_gather_against_fancy_indexing(monkeypatch, slab):
    """Every window of every image (so each edge: x0 and y0 at 0 and at
    the last position), whole and in slabs whose last one is padded,
    equal bit for bit to the slices themselves."""
    import jax.numpy as jnp

    from keystone_tpu.pipelines.images import random_patch_cifar as app

    rng = np.random.default_rng(3)
    images = rng.normal(100, 50, (3, 10, 9, 3)).astype(np.float32)
    k = 4
    img, x0, y0 = (a.ravel().astype(np.int32) for a in np.meshgrid(
        np.arange(3), np.arange(10 - k + 1), np.arange(9 - k + 1),
        indexing="ij"))
    assert x0.max() == 6 and y0.max() == 5 and len(img) == 126
    monkeypatch.setattr(app, "GATHER_SLAB", slab)
    app._gather_windows.clear_cache()  # the slab is read when tracing
    try:
        got = np.asarray(app._gather_windows(
            jnp.asarray(images), img, x0, y0, size=k))
    finally:
        app._gather_windows.clear_cache()
    want = np.stack([
        images[i, x:x + k, y:y + k, :].transpose(1, 0, 2).ravel()
        for i, x, y in zip(img, x0, y0)
    ])
    np.testing.assert_array_equal(got, want)


class _HostNumpy:
    """numpy as ``random_patch_cifar`` sees it, refusing device arrays:
    on the CPU a read-back is a view and no transfer guard sees it."""

    def __getattr__(self, name):
        import jax

        member = getattr(np, name)
        if isinstance(member, type) or not callable(member):
            return member

        def refusing(*args, **kwargs):
            values = list(args) + list(kwargs.values())
            assert not any(isinstance(v, jax.Array) for v in values), name
            return member(*args, **kwargs)

        return refusing


def test_build_filters_reads_nothing_back_and_compiles_once(monkeypatch):
    """Between the index put and the returned arrays nothing comes back
    to the host (the guard is what a TPU enforces; the numpy stand-in
    is what the CPU can show), the bank and the whitener are device
    arrays, and a second fit's other indices reuse the one program."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    images = Dataset.from_array(jnp.asarray(
        _seeded_images(5, np.random.default_rng(8))))
    monkeypatch.setattr(app, "WHITENER_SAMPLE", 400)
    monkeypatch.setattr(app, "np", _HostNumpy())
    before = app._filter_bank._cache_size()
    banks = []
    with jax.transfer_guard_device_to_host("disallow"):
        for seed in (1, 2, 3):
            conf = RandomCifarConfig(num_filters=24, seed=seed)
            filters, whitener = app.build_filters(images, conf)
            assert isinstance(filters, jax.Array)
            assert isinstance(whitener.whitener, jax.Array)
            assert isinstance(whitener.means, jax.Array)
            banks.append(filters)
    assert app._filter_bank._cache_size() - before == 1
    assert not np.allclose(np.asarray(banks[0]), np.asarray(banks[1]))
