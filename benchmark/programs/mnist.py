"""MnistRandomFFT through
``pipelines/images/mnist_random_fft.py:build_pipeline`` and ``.fit()``.

Images are seeded digit-like strokes on 28 x 28, raw 0..255 float32 as
``CsvDataLoader`` leaves MNIST's pixels, made on the device: each class
has a template of a few quadratic strokes inside the central 20 x 20 box,
and each image draws a small affine deformation, a jitter of the strokes'
control points and a stroke width of its own; a pixel's value falls from
255 to 0 over ``edge`` pixels around the stroke's half-width, so most
pixels are exactly 0 (MNIST: about 19% non-zero, mean near 33). The
constants are the configuration's (``assumed.generator``). Ten classes
of equal size, shuffled.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.programs import fold_key
# the block model of a fitted predictor and the release of the program's
# state: as TIMIT's
from benchmark.programs.timit import _model, free  # noqa: F401

SIDE = 28


def digits(key, templates, y, gen: dict):
    """(n, 784) float32 images of classes ``y`` from ``templates``
    (classes, strokes, 3 control points, 2)."""
    import jax
    import jax.numpy as jnp

    n = y.shape[0]
    ka, kj, kw = jax.random.split(key, 3)
    a = jax.random.uniform(ka, (n, 5), minval=-1.0, maxval=1.0)
    angle = float(gen["rotate"]) * a[:, 0]
    scale = 1.0 + float(gen["scale"]) * a[:, 1]
    shear = float(gen["shear"]) * a[:, 2]
    shift = float(gen["shift"]) * a[:, 3:5]
    c, s = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    # rotation and scale, then shear along x, about the box's centre
    m = jnp.stack([jnp.stack([c, -s + shear * c], -1),
                   jnp.stack([s, c + shear * s], -1)], -2)  # (n, 2, 2)
    centre = (SIDE - 1) / 2.0
    pts = templates[y] + float(gen["jitter"]) * jax.random.normal(
        kj, templates[y].shape)  # (n, strokes, 3, 2)
    pts = jnp.einsum("nij,nskj->nski", m, pts - centre) + centre \
        + shift[:, None, None, :]
    width = jax.random.uniform(
        kw, (n,), minval=float(gen["width"][0]), maxval=float(gen["width"][1]))
    t = jnp.linspace(0.0, 1.0, int(gen["segments"]) + 1)
    b = jnp.stack([(1 - t) ** 2, 2 * t * (1 - t), t ** 2], -1)  # Bezier
    curve = jnp.einsum("tk,nskd->nstd", b, pts)  # (n, strokes, t, 2)
    p0 = curve[:, :, :-1].reshape(n, -1, 2)
    d = curve[:, :, 1:].reshape(n, -1, 2) - p0
    yy, xx = jnp.meshgrid(jnp.arange(SIDE, dtype=jnp.float32),
                          jnp.arange(SIDE, dtype=jnp.float32), indexing="ij")
    grid = jnp.stack([xx.ravel(), yy.ravel()], -1)  # (784, 2)

    def nearest(best, j):  # distance of every pixel to segment j
        q = grid[None] - p0[:, j, None]
        dj = d[:, j, None]
        h = jnp.clip(jnp.sum(q * dj, -1)
                     / jnp.maximum(jnp.sum(dj * dj, -1), 1e-6), 0.0, 1.0)
        dist = jnp.sqrt(jnp.sum((q - h[..., None] * dj) ** 2, -1))
        return jnp.minimum(best, dist), None

    dist, _ = jax.lax.scan(nearest, jnp.full((n, SIDE * SIDE), 1e9),
                           jnp.arange(p0.shape[1]))
    edge = float(gen["edge"])
    ink = jnp.clip((width[:, None] + edge / 2 - dist) / edge, 0.0, 1.0)
    return jnp.round(255.0 * ink)


def draw(key, cfg: dict, n: int, n_test: int):
    """(train images, labels, held-out images) from one key."""
    import jax
    import jax.numpy as jnp

    gen = cfg["assumed"]["generator"]
    k = int(cfg["num_classes"])
    kt, ky, ka, kb, kh = jax.random.split(key, 5)
    lo, hi = gen["box"]
    templates = jax.random.uniform(
        kt, (k, int(gen["strokes"]), 3, 2), minval=float(lo),
        maxval=float(hi))
    y = jax.random.permutation(ky, jnp.arange(n) % k).astype(jnp.int32)
    yt = jax.random.randint(kh, (n_test,), 0, k)
    return (digits(ka, templates, y, gen), y,
            digits(kb, templates, yt, gen))


def make_inputs(ctx) -> dict:
    import jax

    from keystone_tpu.loaders import LabeledData
    from keystone_tpu.ops.stats import RandomFFTFeatures
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import mnist_random_fft as app

    if "row_chunk" in {f.name for f in dataclasses.fields(RandomFFTFeatures)}:
        raise SystemExit(
            "benchmark.programs.mnist: this keystone_tpu runs the random-sign "
            "FFT bank as a complex FFT over chunks of row_chunk rows: 200 "
            "FFTs over 15,000 rows in chunks of 8,192 rows need 13 GB of "
            "temporaries a chunk")
    cfg, tr = ctx.config, ctx.traffic
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    n_test = int(tr["heldout_rows"])
    d = int(cfg["image_pixels"])
    if d != app.MNIST_DIM or int(cfg["num_iter"]) != 1 \
            or float(cfg["rectify_threshold"]) != 0.0:
        raise ValueError("784 pixels, one sweep and LinearRectifier(0.0) "
                         "are the application's constants")
    x, y, xt = jax.jit(lambda key: draw(key, cfg, n, n_test))(
        fold_key(ctx.seed))
    conf = app.MnistRandomFFTConfig(
        num_ffts=int(cfg["num_ffts"]), block_size=int(cfg["block_size"]),
        lam=float(cfg["lambda"]), seed=ctx.seed)
    train = LabeledData(labels=Dataset.from_array(y),
                        data=Dataset.from_array(x))
    return {"train": train, "x": x, "y": y, "x_test": xt, "conf": conf,
            "rows": n}


def build(inputs: dict):
    """The application's predictor, as the application builds it."""
    from keystone_tpu.pipelines.images import mnist_random_fft as app

    return app.build_pipeline(inputs["train"], inputs["conf"])


def fit(inputs: dict):
    """One whole fit, as the application makes it (the sign draws, the
    FFT bank, the block solver), ended by block_until_ready on the model.
    The prefix cache is emptied first: with it a second fit of the same
    data would compute nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).W)
    return fitted


def outputs(fitted, inputs: dict) -> dict:
    """The held-out rows through the fitted predictor: the featurizer's
    output and the class scores read before its MaxClassifier; and the
    fitted block model, (features, classes)."""
    from keystone_tpu.ops.stats import RandomFFTFeatures
    from keystone_tpu.ops.util.nodes import MaxClassifier
    from keystone_tpu.parallel.dataset import Dataset

    values = {fitted.source: Dataset.from_array(inputs["x_test"])}
    out, last = {}, None
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        if isinstance(op, MaxClassifier):
            break
        last = values[node] = op.batch_transform(
            [values[dep] for dep in fitted.graph.dependencies[node]])
        if isinstance(op, RandomFFTFeatures):
            out["features"] = np.asarray(last.array())
    out["scores"] = np.asarray(last.array())
    out["W"] = np.asarray(_model(fitted).W)
    return out


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data, nothing the program made."""
    return {"images": np.asarray(inputs["x"]), "y": np.asarray(inputs["y"]),
            "test_images": np.asarray(inputs["x_test"])}
