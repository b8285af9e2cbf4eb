"""The flagship's fit from Fisher vectors to model, through
``pipelines/images/imagenet_sift_lcs_fv.py:fit_classifier`` (the tail of
the application's ``build_pipeline``): integer labels → ±1 indicators →
Cacher → the mixture-weighted block solver at the estimator's defaults →
TopKClassifier(5).

Features are made on the device from the seed, as rows that the
pipeline's own last three nodes (NormalizeRows → SignedHellingerMapper →
NormalizeRows) would emit: a class mean plus noise, both with a power-law
spectrum, under a seeded rotation, then L2 normalisation, signed square
root and L2 normalisation again — unit rows with correlated columns. The
constants are the configuration's ``generator`` group. Class sizes lie
between ``class_size_min`` and 1.0 of the largest.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark.programs import fold_key

CHUNK = 8192  # rows made at a time, so that the noise never fills the chip


def rotation(seed: int, d: int) -> np.ndarray:
    """A seeded (d, d) rotation: Q of the QR of a Gaussian matrix, signs
    fixed so that it does not depend on the LAPACK build."""
    g = np.random.default_rng(seed).standard_normal((d, d))
    q, r = np.linalg.qr(g)
    return (q * np.sign(np.diag(r))).astype(np.float32)


def draw_labels(key, n: int, num_classes: int, size_min: float):
    """(n,) int32 labels, shuffled; class sizes proportional to seeded
    weights in [size_min, 1.0], so the smallest class has about
    ``size_min`` of the largest's rows (ImageNet: 732 to 1,300)."""
    import jax
    import jax.numpy as jnp

    kw, kp = jax.random.split(key)
    u = jax.random.uniform(kw, (num_classes,), minval=size_min, maxval=1.0)
    bounds = jnp.floor(jnp.cumsum(u) / jnp.sum(u) * n)
    y = jnp.searchsorted(bounds, jnp.arange(n, dtype=jnp.float32),
                         side="right")
    y = jnp.minimum(y, num_classes - 1).astype(jnp.int32)
    return jax.random.permutation(kp, y)


def draw_features(key, y, means, spectrum, q):
    """(len(y), d) float32 rows of the labels' classes, made CHUNK rows
    at a time."""
    import jax
    import jax.numpy as jnp

    n, d = y.shape[0], q.shape[0]
    chunk = min(CHUNK, n)
    assert n % chunk == 0, (n, chunk)

    def unit(v):
        return v / jnp.linalg.norm(v, axis=1, keepdims=True)

    def rows(args):
        k, yc = args
        z = (means[yc] + jax.random.normal(k, (chunk, d))) * spectrum
        v = unit(jnp.matmul(z, q, precision="highest"))
        return unit(jnp.sign(v) * jnp.sqrt(jnp.abs(v)))

    keys = jax.random.split(key, n // chunk)
    return jax.lax.map(rows, (keys, y.reshape(-1, chunk))).reshape(n, d)


def make_inputs(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.parallel.dataset import Dataset
    # a program without fit_classifier fails here, before any data is made
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        fit_classifier,  # noqa: F401
    )

    cfg, tr, gen_cfg = ctx.config, ctx.traffic, ctx.config["generator"]
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    n_test = int(tr["heldout_rows"])
    d, c = int(cfg["num_features"]), int(cfg["num_classes"])
    conf = ImageNetSiftLcsFVConfig(
        lam=float(cfg["lambda"]), mixture_weight=float(cfg["mixture_weight"]),
        desc_dim=int(cfg["desc_dim"]), vocab_size=int(cfg["vocab_size"]),
        num_classes=c,
    )
    # what the application hard-codes has to be what the file states
    assert d == 2 * 2 * conf.desc_dim * conf.vocab_size, cfg
    assert int(cfg["block_size"]) == 4096 and int(cfg["num_iter"]) == 1, cfg
    mesh_lib.set_mesh(mesh_lib.make_mesh(devices=ctx.devices))
    q = jnp.asarray(rotation(ctx.seed, d))
    spectrum = jnp.arange(1, d + 1, dtype=jnp.float32) ** (
        -0.5 * float(gen_cfg["spectrum_decay"]))

    def gen(key, q, spectrum):
        km, ky, kx, kyt, kxt = jax.random.split(key, 5)
        means = float(gen_cfg["class_scale"]) * jax.random.normal(km, (c, d))
        y = draw_labels(ky, n, c, float(gen_cfg["class_size_min"]))
        yt = jax.random.randint(kyt, (n_test,), 0, c)
        return (draw_features(kx, y, means, spectrum, q), y,
                draw_features(kxt, yt, means, spectrum, q))

    # q is an argument, not a constant of the program: with it inside,
    # the executable is too large for the persistent compile cache
    x, y, xt = jax.jit(gen)(fold_key(ctx.seed), q, spectrum)
    return {"features": Dataset.from_array(x), "labels": Dataset.from_array(y),
            "x": x, "y": y, "x_test": xt, "conf": conf, "rows": n}


def build(inputs: dict):
    """The application's own solver tail on features that are already
    there: ``Identity()`` stands where ``build_pipeline`` has its
    featurize branches."""
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        fit_classifier,
    )
    from keystone_tpu.workflow.api import Identity

    return fit_classifier(Identity(), inputs["features"], inputs["labels"],
                          inputs["conf"])


def fit(inputs: dict):
    """One whole fit, ended by block_until_ready on the model. The prefix
    cache is emptied first: with it a second fit of the same data would
    compute nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).W)
    return fitted


def _model(fitted):
    return next(op for op in fitted.graph.operators.values()
                if hasattr(op, "W") and hasattr(op, "block_size"))


def outputs(fitted, inputs: dict) -> dict:
    """The fitted model itself and the class scores of the held-out rows
    through the fitted predictor, read before its TopKClassifier (the
    top-5 would hide a model that is a little off)."""
    from keystone_tpu.ops.util.nodes import TopKClassifier
    from keystone_tpu.parallel.dataset import Dataset

    values = {fitted.source: Dataset.from_array(inputs["x_test"])}
    out = None
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        if isinstance(op, TopKClassifier):
            break
        out = values[node] = op.batch_transform(
            [values[dep] for dep in fitted.graph.dependencies[node]])
    model = _model(fitted)
    return {"scores": np.asarray(out.array()), "W": np.asarray(model.W),
            "intercept": np.asarray(model.intercept)}


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data, nothing the program made."""
    return {"x": np.asarray(inputs["x"]), "y": np.asarray(inputs["y"]),
            "x_test": np.asarray(inputs["x_test"])}


def free(inputs: dict) -> None:
    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    inputs.clear()
    gc.collect()
