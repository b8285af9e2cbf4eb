"""TIMIT through ``pipelines/speech/timit.py:build_pipeline``.

Frames are seeded Gaussians and labels come from a planted linear model
plus noise, all made on the device(s) in one jitted call; rows are
sharded over the mesh's data axis (a 1x1 mesh on one chip).
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark.programs import fold_key


def make_inputs(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.speech import timit

    cfg, tr = ctx.config, ctx.traffic
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    n_test = int(tr["heldout_rows"])
    d, k = int(cfg["dim"]), int(cfg["num_classes"])
    mesh = mesh_lib.make_mesh(devices=ctx.devices)
    mesh_lib.set_mesh(mesh)
    rows2 = mesh_lib.data_sharding(mesh, ndim=2)
    rows1 = mesh_lib.data_sharding(mesh, ndim=1)
    noise = float(tr.get("label_noise", 1.0))

    def gen(key):
        kx, kt, kw, kn, km = jax.random.split(key, 5)
        x = jax.random.normal(kx, (n, d), jnp.float32)
        xt = jax.random.normal(kt, (n_test, d), jnp.float32)
        w = jax.random.normal(kw, (d, k), jnp.float32) / np.sqrt(d)
        s = jnp.matmul(x, w, precision="highest")
        y = jnp.argmax(s + noise * jax.random.normal(kn, s.shape), axis=1)
        return x, y.astype(jnp.int32), xt

    x, y, xt = jax.jit(gen, out_shardings=(rows2, rows1, rows2))(
        fold_key(ctx.seed))
    conf = timit.TimitConfig(
        num_cosines=int(cfg["numCosines"]), gamma=float(cfg["gamma"]),
        num_epochs=int(cfg["numEpochs"]), lam=float(cfg["lambda"]),
        rf_type=cfg["rfType"], seed=ctx.seed,
        num_cosine_features=int(cfg["num_cosine_features"]), dim=d,
        num_classes=k,
    )
    train = LabeledData(labels=Dataset.from_array(y),
                        data=Dataset.from_array(x))
    return {"train": train, "x": x, "y": y, "x_test": xt, "conf": conf,
            "rows": n, "solve": cfg.get("solve", "device")}


def build(inputs: dict):
    """The application's predictor. ``solve: device`` (the estimator's
    default) is ``timit.build_pipeline`` itself. ``solve: host`` is the
    same graph, built here node for node, with the estimator's own
    ``solve="host"`` option (the (b, b) systems solved on the host in
    float64, the reference's driver-side solve), which
    ``build_pipeline`` has no argument for."""
    from keystone_tpu.pipelines.speech import timit

    train, conf = inputs["train"], inputs["conf"]
    if inputs["solve"] == "device":
        return timit.build_pipeline(train, conf)
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.util.nodes import (
        ClassLabelIndicators, MaxClassifier, VectorCombiner)
    from keystone_tpu.workflow.api import Pipeline

    labels = ClassLabelIndicators(conf.num_classes)(train.labels)
    branches = [
        CosineRandomFeatures.create(
            conf.dim, conf.num_cosine_features, conf.gamma,
            seed=conf.seed + i, distribution=conf.rf_type)
        for i in range(conf.num_cosines)
    ]
    featurizer = Pipeline.gather(branches).and_then(VectorCombiner())
    return featurizer.and_then(
        BlockLeastSquaresEstimator(
            conf.num_cosine_features, num_iter=conf.num_epochs,
            lam=conf.lam, solve=inputs["solve"]),
        train.data, labels,
    ).and_then(MaxClassifier())


def fit(inputs: dict):
    """One whole fit, as the application makes it, ended by
    block_until_ready on the model. The prefix cache is emptied first:
    with it a second fit of the same data would compute nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).W)
    return fitted


def _model(fitted):
    return next(op for op in fitted.graph.operators.values()
                if hasattr(op, "W") and hasattr(op, "block_size"))


def outputs(fitted, inputs: dict) -> np.ndarray:
    """Class scores of the held-out rows through the fitted predictor,
    read before its MaxClassifier (the argmax would hide a model that is
    a little off)."""
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.ops.util.nodes import MaxClassifier

    values = {fitted.source: Dataset.from_array(inputs["x_test"])}
    out = None
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        if isinstance(op, MaxClassifier):
            break
        out = values[node] = op.batch_transform(
            [values[dep] for dep in fitted.graph.dependencies[node]])
    return np.asarray(out.array())


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data and the configuration, nothing the program made."""
    return {"x": np.asarray(inputs["x"]), "y": np.asarray(inputs["y"]),
            "x_test": np.asarray(inputs["x_test"])}


def free(inputs: dict) -> None:
    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    inputs.clear()
    gc.collect()
