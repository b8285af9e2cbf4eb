"""RandomPatchCifar through
``pipelines/images/random_patch_cifar.py:build_pipeline``.

Images are seeded textures in the range of uint8 as ``CifarLoader``
leaves them: one array-mode ``Dataset`` of float32 ``(rows, 32, 32, 3)``
on the device. Each is a class template plus a texture of its own, both
sums of Gaussian fields at several scales with most of the weight on the
coarse ones and the three channels tied by a shared luminance, so that
the ZCA whitener sees a spectrum that falls with frequency; then pixel
noise, rounding and clipping to 0..255. The constants are the
configuration's (``assumed.generator``). Ten classes of equal size,
shuffled.
"""

from __future__ import annotations

import numpy as np

from benchmark.programs import fold_key
# the block model of a fitted predictor, the held-out scores read before
# MaxClassifier and the release of the program's state: as TIMIT's
from benchmark.programs.timit import _model, free, outputs  # noqa: F401


def _fields(key, count: int, side: int, channels: int, gen: dict):
    """``count`` random fields of unit variance, (count, side, side, C)."""
    import jax
    import jax.numpy as jnp

    total, power = 0.0, 0.0
    for scale, weight in zip(gen["scales"], gen["scale_weights"]):
        key, kl, kc = jax.random.split(key, 3)
        coarse = jax.random.normal(kl, (count, scale, scale, 1)) \
            + float(gen["chroma"]) * jax.random.normal(
                kc, (count, scale, scale, channels))
        fine = jax.image.resize(
            coarse, (count, side, side, channels), "bilinear")
        total = total + float(weight) * fine
        power = power + float(weight) ** 2
    total = total / jnp.sqrt(power)
    return total / jnp.std(total)


def make_inputs(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.workflow import api

    if not hasattr(api, "RowwiseRun"):
        raise SystemExit(
            "benchmark.programs.cifar: this keystone_tpu cannot take a run "
            "of row-wise nodes through in chunks (no workflow.api."
            "RowwiseRun): 10,000 filters' maps do not fit a chip whole")
    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    cfg, tr = ctx.config, ctx.traffic
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    n_test = int(tr["heldout_rows"])
    side, _, channels = cfg["image"]
    k = int(cfg["num_classes"])
    gen = cfg["assumed"]["generator"]
    if int(cfg["whitener_sample"]) != app.WHITENER_SAMPLE:
        raise ValueError("whitener_sample is the application's constant")

    def images(key, templates, count):
        ky, kt, kn = jax.random.split(key, 3)
        y = jax.random.permutation(ky, jnp.arange(count) % k)
        signal = float(gen["class_signal"]) * templates[y] \
            + float(gen["texture"]) * _fields(kt, count, side, channels, gen)
        pixels = float(gen["mean"]) + float(gen["contrast"]) * signal \
            + float(gen["pixel_noise"]) * jax.random.normal(kn, signal.shape)
        return jnp.clip(jnp.round(pixels), 0.0, 255.0), y.astype(jnp.int32)

    def draw(key):
        kc, ka, kb = jax.random.split(key, 3)
        templates = _fields(kc, k, side, channels, gen)
        x, y = images(ka, templates, n)
        xt, _ = images(kb, templates, n_test)
        return x, y, xt

    x, y, xt = jax.jit(draw)(fold_key(ctx.seed))
    conf = app.RandomCifarConfig(
        num_filters=int(cfg["num_filters"]),
        whitening_epsilon=float(cfg["whitening_epsilon"]),
        patch_size=int(cfg["patch_size"]),
        patch_steps=int(cfg["patch_steps"]),
        pool_size=int(cfg["pool_size"]), pool_stride=int(cfg["pool_stride"]),
        alpha=float(cfg["alpha"]), lam=float(cfg["lambda"]),
        block_size=int(cfg["block_size"]), seed=ctx.seed,
    )
    train = LabeledImages(labels=Dataset.from_array(y),
                          images=Dataset.from_array(x))
    return {"train": train, "x": x, "y": y, "x_test": xt, "conf": conf,
            "rows": n}


def build(inputs: dict):
    """The application's predictor, as the application builds it."""
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    return app.build_pipeline(inputs["train"], inputs["conf"])


def fit(inputs: dict):
    """One whole fit, as the application makes it (filters from the
    training images, the featurizer, the scaler, the block solver),
    ended by block_until_ready on the model. The prefix cache is emptied
    first: with it a second fit of the same data would compute nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).W)
    return fitted


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data, nothing the program made."""
    return {"images": np.asarray(inputs["x"]), "y": np.asarray(inputs["y"]),
            "test_images": np.asarray(inputs["x_test"])}
