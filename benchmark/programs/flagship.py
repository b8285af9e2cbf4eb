"""The flagship featurize-and-score path: ``serving/featurize.py:
flagship_pipeline(rng)`` (seeded PCA and GMM, the dataflow of
``imagenet_sift_lcs_fv.build_pipeline`` before the solver), a seeded
(4096, 1000) model and ``TopKClassifier(5)``, applied as the application
applies its predictor: ``predictor(Dataset.from_items(images)).get()``.

Images are seeded oriented textures (chip_smoke.py's, PR 21), made on the
device in one jitted call and handed over as host uint8 arrays, as the
loader hands them over, so a step pays the upload.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmark.programs import fold_key


def textures(seed: int, n: int, size: int) -> np.ndarray:
    """(n, size, size, 3) uint8: a class-dependent oriented texture and
    tint plus noise, class drawn per image."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        kc, kn = jax.random.split(key)
        c = jax.random.randint(kc, (n,), 0, 1000).astype(jnp.float32)
        y, x = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                            jnp.arange(size, dtype=jnp.float32),
                            indexing="ij")
        theta = (c * 0.61803398875) % jnp.pi
        ct, st = jnp.cos(theta)[:, None, None], jnp.sin(theta)[:, None, None]
        u = x * ct + y * st
        v = y * ct - x * st
        fx = (2.0 + 0.45 * (c % 40))[:, None, None]
        fy = (2.5 + 0.9 * (c // 40))[:, None, None]
        base = jnp.sin(u / fx) * jnp.cos(v / fy)
        tint = 0.7 + 0.3 * jnp.sin(
            c[:, None] * jnp.asarray([0.37, 0.59, 0.83]))
        img = (128.0 + 90.0 * base[..., None] * tint[:, None, None, :]
               + 8.0 * jax.random.normal(kn, (n, size, size, 3)))
        return jnp.clip(img, 0, 255).astype(jnp.uint8)

    return np.asarray(jax.jit(gen)(fold_key(seed)))


def make_inputs(ctx) -> dict:
    import jax.numpy as jnp

    from keystone_tpu.ops.learning.block_ls import BlockLinearMapper
    from keystone_tpu.ops.util.nodes import TopKClassifier
    from keystone_tpu.serving.featurize import flagship_pipeline

    import jax

    cfg, tr = ctx.config, ctx.traffic
    if cfg.get("default_matmul_precision"):
        # the configuration states float32: products that the program
        # gives no precision run at this one, not at the backend's default
        # (one bf16 pass on a TPU); PERF.md, Open questions
        jax.config.update("jax_default_matmul_precision",
                          cfg["default_matmul_precision"])
    n = int(tr["images_per_step"])
    images = textures(ctx.seed, n, int(cfg["image_size"]))
    rng = np.random.default_rng(ctx.seed)
    featurizer = flagship_pipeline(
        rng, int(cfg["desc_dim"]), int(cfg["vocab_size"]),
        sift_step=int(cfg["sift_step"]), sift_bin=int(cfg["sift_bin"]),
        sift_scales=int(cfg["sift_scales"]),
        sift_scale_step=int(cfg["sift_scale_step"]),
        lcs_stride=int(cfg["lcs_stride"]), lcs_border=int(cfg["lcs_border"]),
        lcs_patch=int(cfg["lcs_patch"]),
    )
    feats = 2 * 2 * int(cfg["desc_dim"]) * int(cfg["vocab_size"])
    k = int(cfg["num_classes"])
    w = (rng.standard_normal((feats, k)) * float(cfg["model_scale"])
         ).astype(np.float32)
    b = rng.standard_normal(k).astype(np.float32)
    model = BlockLinearMapper(jnp.asarray(w), feats, label_mean=jnp.asarray(b))
    return {
        "images": images,
        "items": [images[i] for i in range(n)],
        "scorer": featurizer.and_then(model),
        "predictor": featurizer.and_then(model).and_then(
            TopKClassifier(int(cfg["top_k"]))),
        "work": n,
    }


def score(inputs: dict, which: str = "predictor") -> np.ndarray:
    """One step: the predictor applied to the whole Dataset of host
    images, ended by reading the result back. The prefix cache is
    emptied first: with it a second application would compute nothing."""
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    out = inputs[which](Dataset.from_items(inputs["items"])).get()
    return np.asarray(out.array())


def free(inputs: dict) -> None:
    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.get_or_create().reset()
    inputs.clear()
    gc.collect()
