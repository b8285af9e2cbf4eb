"""RandomPatchCifarAugmentedKernel through
``pipelines/images/cifar_apps.py:build_augmented_kernel_pipeline``.

Images are ``programs/cifar.py``'s seeded textures (the same generator
and constants, the configuration's ``assumed.generator``): one array-mode
``Dataset`` of float32 ``(images, 32, 32, 3)`` on the device, ten
classes of equal size, shuffled. ``rows_per_chip`` counts the rows the
solver fits, ``augment_copies`` crops an image; ``heldout_rows`` the
held-out crops, ten an image (the application's ``CenterCornerPatcher``
with flips). The crops, the flips, the filters and the block order are
the application's own, made anew from the training images in every fit.
"""

from __future__ import annotations

import numpy as np

from benchmark.programs import fold_key
from benchmark.programs.cifar import _fields
from benchmark.programs.timit import free  # noqa: F401


def make_inputs(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.pipelines.images import cifar_apps as app

    if not hasattr(app, "build_augmented_kernel_pipeline"):
        raise SystemExit(
            "benchmark.programs.cifar_krr: this keystone_tpu has no "
            "pipelines.images.cifar_apps.build_augmented_kernel_pipeline: "
            "the application builds, fits, scores and evaluates in one "
            "function and gives the benchmark no lazy pipeline to fit")
    from keystone_tpu.loaders.cifar import LabeledImages
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import random_patch_cifar

    cfg, tr = ctx.config, ctx.traffic
    copies = int(cfg["augment_copies"])
    rows = int(tr["rows_per_chip"]) * len(ctx.devices)
    n, n_test = rows // copies, int(tr["heldout_rows"]) // 10
    side, _, channels = cfg["image"]
    k = int(cfg["num_classes"])
    gen = cfg["assumed"]["generator"]
    if int(cfg["whitener_sample"]) != random_patch_cifar.WHITENER_SAMPLE:
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
    conf = app.RandomCifarAugmentedKernelConfig(
        num_filters=int(cfg["num_filters"]),
        whitening_epsilon=float(cfg["whitening_epsilon"]),
        patch_size=int(cfg["patch_size"]),
        patch_steps=int(cfg["patch_steps"]),
        pool_size=int(cfg["pool_size"]), pool_stride=int(cfg["pool_stride"]),
        alpha=float(cfg["alpha"]), lam=float(cfg["lambda"]),
        block_size=int(cfg["block_size"]), seed=ctx.seed,
        augment_patch_size=int(cfg["augment_patch_size"]),
        augment_copies=copies, gamma=float(cfg["gamma"]),
        num_epochs=int(cfg["num_epochs"]),
        flip_chance=float(cfg["flip_chance"]),
    )
    train = LabeledImages(labels=Dataset.from_array(y),
                          images=Dataset.from_array(x))
    inputs = {"train": train, "x": x, "y": y, "x_test": xt, "conf": conf,
              "rows": rows}
    warm_memory_states(inputs)
    return inputs


def warm_memory_states(inputs: dict) -> None:
    """Set-up's share of the warm-up that the driver cannot know of: a
    fit beside none, one, two and three fitted models. ``fit_loop``
    keeps up to three of the window's models, each holding its training
    rows (2.05 GB), and ``RowwiseRun`` plans its chunks from the memory
    the device has free, so a fit beside three kept models runs another
    chunk program than one beside none (16 chunks for 8: my chip run,
    PR 33); warmed here, nothing compiles in the window."""
    held = []
    for _ in range(4):
        held.append(fit(inputs))
    del held


def build(inputs: dict):
    """The application's predictor, as the application builds it."""
    from keystone_tpu.pipelines.images import cifar_apps as app

    return app.build_augmented_kernel_pipeline(
        inputs["train"], inputs["conf"])


def _model(fitted):
    return next(op for op in fitted.graph.operators.values()
                if hasattr(op, "kernel_transformer"))


def fit(inputs: dict):
    """One whole fit, as the application makes it (crops, flips and
    filters from the training images, the featurizer, the scaler, the
    kernel solver), ended by block_until_ready on the dual model. The
    prefix cache is emptied first: with it a second fit of the same data
    would compute nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).model)
    return fitted


def outputs(fitted, inputs: dict) -> np.ndarray:
    """Class scores of the held-out images' ten centre-and-corner crops
    (flipped and not) through the fitted predictor: what the augmented
    evaluator is given, before it merges an image's copies."""
    from keystone_tpu.ops.images import CenterCornerPatcher
    from keystone_tpu.parallel.dataset import Dataset

    size = inputs["conf"].augment_patch_size
    crops = CenterCornerPatcher(size, size, horizontal_flips=True) \
        .apply_batch(Dataset.from_array(inputs["x_test"]))
    return np.asarray(fitted(crops).array())


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data, nothing the program made."""
    return {"images": np.asarray(inputs["x"]), "y": np.asarray(inputs["y"]),
            "test_images": np.asarray(inputs["x_test"])}
