"""VOCSIFTFisher through
``pipelines/images/voc_sift_fisher.py:build_pipeline`` and ``.fit()``: the
PCA, the GMM and the block model are fitted inside every step, from the
images.

Images are seeded oriented textures by ``programs/flagship.py:
textures``' rule, made at each of the configuration's sizes (``sizes``: W,
H, share) and handed over as ``VOCLoader`` leaves them: a list of host
uint8 (H, W, 3) arrays of several shapes. An image shows one to three of
the 20 classes (the mean of their textures) and its labels are their ±1
indicators. Each size's share is met exactly, the order is drawn from the
seed.
"""

from __future__ import annotations

import numpy as np

from benchmark.programs import fold_key
from benchmark.programs.timit import _model, free  # noqa: F401


def shape_counts(sizes, n: int) -> list:
    """[((H, W), images)]: each share of ``n`` met by largest remainders."""
    want = [n * float(share) for _, _, share in sizes]
    counts = [int(w) for w in want]
    for i in sorted(range(len(sizes)), key=lambda i: counts[i] - want[i]):
        if sum(counts) == n:
            break
        counts[i] += 1
    return [((int(h), int(w)), c) for (w, h, _), c in zip(sizes, counts)]


def textures(key, classes: np.ndarray, h: int, w: int):
    """(n, h, w, 3) uint8: the mean of each image's classes' oriented
    textures (``classes`` (n, 3), -1 where an image has fewer), tinted,
    plus pixel noise."""
    import jax
    import jax.numpy as jnp

    def gen(key, classes):
        c = jnp.maximum(classes, 0).astype(jnp.float32) * 50.0  # of 1000
        on = (classes >= 0).astype(jnp.float32)
        y, x = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                            jnp.arange(w, dtype=jnp.float32), indexing="ij")
        theta = (c * 0.61803398875) % jnp.pi
        ct = jnp.cos(theta)[..., None, None]
        st = jnp.sin(theta)[..., None, None]
        u, v = x * ct + y * st, y * ct - x * st
        fx = (2.0 + 0.45 * (c % 40))[..., None, None]
        fy = (2.5 + 0.9 * (c // 40))[..., None, None]
        base = jnp.sin(u / fx) * jnp.cos(v / fy)  # (n, 3, h, w)
        tint = 0.7 + 0.3 * jnp.sin(
            c[..., None] * jnp.asarray([0.37, 0.59, 0.83]))  # (n, 3, 3)
        each = base[..., None] * tint[:, :, None, None, :]
        mean = jnp.sum(each * on[:, :, None, None, None], axis=1) \
            / jnp.sum(on, axis=1)[:, None, None, None]
        img = 128.0 + 90.0 * mean + 8.0 * jax.random.normal(
            key, (classes.shape[0], h, w, 3))
        return jnp.clip(img, 0, 255).astype(jnp.uint8)

    return np.asarray(jax.jit(gen)(key, jnp.asarray(classes)))


def make_images(cfg: dict, seed: int, n: int, stream: int) -> tuple:
    """(items, classes (n, 3)) of ``n`` images; ``stream`` keeps the
    training and the held-out draws apart."""
    import jax

    rng = np.random.default_rng((seed, stream))
    k = int(cfg["num_classes"])
    count = rng.choice([1, 2, 3], size=n, p=[0.6, 0.3, 0.1])
    classes = np.full((n, 3), -1, np.int32)
    for i in range(n):
        classes[i, :count[i]] = rng.choice(k, size=count[i], replace=False)
    order = rng.permutation(n)
    items, at = [None] * n, 0
    key = jax.random.fold_in(fold_key(seed), stream)
    for j, ((h, w), c) in enumerate(shape_counts(cfg["sizes"], n)):
        places = order[at:at + c]
        at += c
        if not c:
            continue
        imgs = textures(jax.random.fold_in(key, j), classes[places], h, w)
        for row, i in enumerate(places):
            items[i] = imgs[row]
    return items, classes


def indicators(classes: np.ndarray, k: int) -> np.ndarray:
    y = -np.ones((classes.shape[0], k), np.float32)
    for i, row in enumerate(classes):
        y[i, row[row >= 0]] = 1.0
    return y


def make_inputs(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.parallel.dataset import Dataset

    if not hasattr(Dataset, "from_groups"):
        raise SystemExit(
            "benchmark.programs.voc: this keystone_tpu keeps ragged images "
            "as items and every node's output whole (no Dataset."
            "from_groups): 313 images of VOC's sizes ask for 12 GB of "
            "descriptors beside 7 GB of reduced ones, which no chip holds")
    from keystone_tpu.pipelines.images import voc_sift_fisher as app

    cfg, tr = ctx.config, ctx.traffic
    jax.config.update("jax_default_matmul_precision",
                      cfg["default_matmul_precision"])
    n = int(tr["rows_per_chip"]) * len(ctx.devices)
    items, classes = make_images(cfg, ctx.seed, n, 0)
    test_items, _ = make_images(cfg, ctx.seed, int(tr["heldout_rows"]), 1)
    k = int(cfg["num_classes"])
    conf = app.SIFTFisherConfig(
        lam=float(cfg["lambda"]), desc_dim=int(cfg["desc_dim"]),
        vocab_size=int(cfg["vocab_size"]),
        scale_step=int(cfg["sift_scale_step"]),
        num_pca_samples=int(cfg["num_pca_samples"]),
        num_gmm_samples=int(cfg["num_gmm_samples"]),
        num_classes=k, seed=ctx.seed,
    )
    return {"items": items, "test_items": test_items,
            "labels": jnp.asarray(indicators(classes, k)), "conf": conf,
            "rows": n}


def build(inputs: dict):
    """The application's predictor, as the application builds it, on the
    host images as a new ``Dataset`` (so that a step pays the upload)."""
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.pipelines.images import voc_sift_fisher as app

    return app.build_pipeline(
        Dataset.from_items(inputs["items"]),
        Dataset.from_array(inputs["labels"]), inputs["conf"],
    )


def fit(inputs: dict):
    """One whole fit, as the application makes it: ``build_pipeline`` and
    ``.fit()`` (the PCA, the GMM and the block model from the images),
    ended by block_until_ready on the block model. The prefix cache is
    emptied first: with it a second fit of the same data would compute
    nothing."""
    import jax

    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build(inputs).fit()
    jax.block_until_ready(_model(fitted).W)
    return fitted


def _through(fitted, items) -> tuple:
    """(features, scores) of ``items``: the fitted predictor node by
    node, the block model's input kept beside its output, and a node's
    output let go once its last reader has run."""
    from keystone_tpu.parallel.dataset import Dataset

    model = _model(fitted)
    deps = fitted.graph.dependencies
    readers: dict = {}
    for node in fitted._topo:
        for dep in deps[node]:
            readers[dep] = readers.get(dep, 0) + 1
    values = {fitted.source: Dataset.from_items(items)}
    feats = out = None
    for node in fitted._topo:
        op = fitted.graph.operators[node]
        ins = [values[dep] for dep in deps[node]]
        if op is model:
            feats = np.asarray(ins[0].array())
        out = values[node] = op.batch_transform(ins)
        for dep in deps[node]:
            readers[dep] -= 1
            if not readers[dep]:
                del values[dep]
        del ins
    return feats, np.asarray(out.array())


def _memory_mark(what: str) -> None:
    """One stderr line of the device's bytes in use and their peak so
    far: which of a run's passes set ``memory_peak_bytes``."""
    import sys

    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[benchmark.programs.voc] {what}: in use "
              f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB, peak so far "
              f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB",
              file=sys.stderr, flush=True)


def outputs(fitted, inputs: dict) -> dict:
    """What the staged comparison reads of one fitted predictor: the PCA
    basis, the GMM with its fit's record, and the features and scores of
    the held-out and of the training images (no evaluator follows the
    block model, so the scores are read as they are)."""
    from keystone_tpu.workflow.executor import PipelineEnv

    _memory_mark("after a fit")
    # the last fit's cached descriptors (7 GB) make room for this pass's
    PipelineEnv.get_or_create().reset()
    ops = list(fitted.graph.operators.values())
    pca = next(op for op in ops if hasattr(op, "pca_mat"))
    gmm = next(op for op in ops if hasattr(op, "gmm")).gmm
    feats, scores = _through(fitted, inputs["test_items"])
    train_feats, _ = _through(fitted, inputs["items"])
    _memory_mark("after the predictor's two passes")
    info = gmm.fit_info
    return {
        "pca": np.asarray(pca.pca_mat),
        "means": np.asarray(gmm.means),
        "variances": np.asarray(gmm.variances),
        "weights": np.asarray(gmm.weights),
        "iterations": int(info["iterations"]), "reason": info["reason"],
        "seeds": np.asarray(info["seeds"]),
        "features": feats, "scores": scores, "train_features": train_feats,
    }


def reference_inputs(inputs: dict) -> dict:
    """Host copies of what the reference may share with the program: the
    benchmark's own data, nothing the program made."""
    return {"items": inputs["items"], "test_items": inputs["test_items"],
            "labels": np.asarray(inputs["labels"]),
            "seed": int(inputs["conf"].seed)}
