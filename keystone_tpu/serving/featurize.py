"""Reference device-side featurize chains for serving.

``CompiledPipeline(featurize=...)`` fuses any fitted pure-JAX pipeline
in front of the model; this module provides the canonical image chains
the ``--device-featurize`` gateway modes, ``benchmark/programs/flagship.py``
and the smoke scripts and tests all share. Nothing here imports a
benchmark program (``tests/test_import_direction.py``).

Two chains:

- ``build_featurize_pipeline`` — the *demo* dense-conv stack
  (PixelScaler → Convolver → rectify → pool → vectorize), the cheap
  geometry the PR-14 plumbing was proven on;
- ``build_flagship_featurize_pipeline`` — the paper's flagship
  ImageNetSiftLcsFV featurization: a **branched** DAG (gray→SIFT and
  LCS branches, each PCA → GMM Fisher Vector → Hellinger/L2
  normalization, gathered through ``VectorCombiner``) whose hot loops
  run as Pallas kernels (``ops/images/pallas_kernels``, ``fv_pallas``).
  Fittable-then-frozen: pass ``fit_images`` to fit real PCA/GMM
  parameters through the reference estimator path, or let the seeded
  warm-start stand in where a deterministic chain is what matters
  (gateway startup, the benchmark, tests). Either way the result is a frozen
  pure-JAX ``FittedPipeline`` that ``CompiledPipeline(featurize=)``
  fuses — branches and all — into each per-bucket XLA program.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Optional, Tuple

import numpy as np


def build_featurize_pipeline(
    img: int = 16,
    channels: int = 3,
    filters: int = 96,
    conv_size: int = 5,
    pool_stride: int = 6,
    pool_size: int = 6,
    seed: int = 7,
) -> Tuple[object, int]:
    """A pure-JAX image featurize chain — raw ``(img, img, C)`` uint8
    in, ``(F,)`` f32 features out: PixelScaler → Convolver (patch
    normalization folded around one XLA conv) → SymmetricRectifier →
    sum-Pooler → channel-major ImageVectorizer, the
    RandomPatchCifar-style dense-conv stack from ``ops/images``.
    Returns ``(fitted_featurize, feature_dim)``. The default geometry
    is the device-featurize demo shape: 16·16·3 = 768 raw uint8
    bytes per example featurize to 768 f32 features = 3072 bytes, so
    shipping raw instead of featurized is a 4× H2D reduction."""
    import jax.numpy as jnp

    from keystone_tpu.ops.images.core import (
        Convolver,
        ImageVectorizer,
        PixelScaler,
        Pooler,
        SymmetricRectifier,
    )

    rng = np.random.default_rng(seed)
    packed = jnp.asarray(
        rng.standard_normal(
            (filters, conv_size * conv_size * channels)
        ).astype(np.float32) * 0.1
    )
    pipe = None
    for node in (
        PixelScaler(),
        Convolver(packed, img, img, channels),
        SymmetricRectifier(),
        Pooler(stride=pool_stride, pool_size=pool_size),
        ImageVectorizer(),
    ):
        pipe = node.to_pipeline() if pipe is None else pipe.and_then(node)
    fitted = pipe.to_pipeline().fit()
    feat_dim = int(
        np.asarray(
            fitted._batch_run(
                jnp.zeros((1, img, img, channels), jnp.uint8)
            )
        ).shape[-1]
    )
    return fitted, feat_dim


def flagship_pipeline(
    rng: np.random.Generator,
    desc_dim: int = 64,
    vocab: int = 16,
    *,
    sift_step: int = 3,
    sift_bin: int = 4,
    sift_scales: int = 4,
    sift_scale_step: int = 1,
    lcs_stride: int = 4,
    lcs_border: int = 16,
    lcs_patch: int = 6,
):
    """The unfitted warm-start ImageNetSiftLcsFV featurize chain —
    everything in ``pipelines/images/imagenet_sift_lcs_fv.build_pipeline``
    before the solver, with seeded random PCA projections and unit
    GMMs standing in for the fitted parameters (the shape/dataflow is
    identical; only the learned values differ). The FV node follows the
    reference's k >= 32 physical choice: the fused Pallas statistics
    kernel for large vocabularies, the plain XLA program below it."""
    import jax.numpy as jnp

    from keystone_tpu.ops.images.fisher_vector import (
        FisherVector,
        FisherVectorFused,
    )
    from keystone_tpu.ops.learning import BatchPCATransformer
    from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
    from keystone_tpu.ops.util.nodes import VectorCombiner
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        fisher_branch,
        lcs_prefix,
        sift_prefix,
    )
    from keystone_tpu.workflow.api import Pipeline

    def branch(prefix, in_dim):
        pca = jnp.asarray(
            rng.standard_normal((desc_dim, in_dim)).astype(np.float32)
            * 0.1
        )
        gmm = GaussianMixtureModel(
            jnp.asarray(
                rng.standard_normal((desc_dim, vocab)), jnp.float32
            ),
            jnp.ones((desc_dim, vocab), jnp.float32),
            jnp.ones((vocab,), jnp.float32) / vocab,
        )
        fv = (
            FisherVectorFused(gmm) if vocab >= 32 else FisherVector(gmm)
        )
        return fisher_branch(prefix, BatchPCATransformer(pca.T), fv)

    sift = branch(
        sift_prefix(
            step=sift_step, bin=sift_bin, num_scales=sift_scales,
            scale_step=sift_scale_step,
        ),
        128,
    )
    lcs = branch(lcs_prefix(lcs_stride, lcs_border, lcs_patch), 96)
    return Pipeline.gather([sift, lcs]).and_then(VectorCombiner())


def build_flagship_featurize_pipeline(
    img: int = 64,
    desc_dim: int = 16,
    vocab: int = 16,
    *,
    sift_step: int = 4,
    sift_bin: int = 4,
    sift_scales: int = 2,
    sift_scale_step: int = 1,
    lcs_stride: int = 4,
    lcs_border: int = 16,
    lcs_patch: int = 6,
    seed: int = 7,
    fit_images: Optional[Any] = None,
) -> Tuple[object, int]:
    """The flagship SIFT+LCS→FV featurize chain as a frozen serving
    stage — raw ``(img, img, 3)`` uint8 in, ``(2·2·desc_dim·vocab,)``
    f32 features out. Returns ``(fitted_featurize, feature_dim)``.

    With ``fit_images`` (a ``Dataset`` of ``(img, img, 3)`` images, or
    an array convertible to one) the PCA projections and GMMs are FIT
    through the reference estimator path
    (``compute_pca_and_fisher_branch``: ColumnSampler → ColumnPCA,
    sampled+projected descriptors → GMM); without it, a seeded
    warm-start stands in (``flagship_pipeline``) — deterministic
    parameters, identical graph, which is what gateway startup and the
    fingerprint tests need. Both paths freeze to
    the same pure-JAX branched DAG; ``feature_dim`` is probed off a
    zero image through ``_batch_run`` — the exact staging surface the
    serving engine fuses.

    The default geometry (64² raw, 2 SIFT scales, 16-word vocab) keeps
    the CPU smoke under a minute while exercising every node class of
    the full-size chain; ``img`` must cover the LCS border
    (``img > 2·lcs_border``) and the SIFT sampling bounds."""
    import jax.numpy as jnp

    if img <= 2 * lcs_border:
        raise ValueError(
            f"img={img} leaves the LCS keypoint grid empty "
            f"(needs img > 2*lcs_border = {2 * lcs_border})"
        )
    if fit_images is None:
        pipe = flagship_pipeline(
            np.random.default_rng(seed), desc_dim, vocab,
            sift_step=sift_step, sift_bin=sift_bin,
            sift_scales=sift_scales, sift_scale_step=sift_scale_step,
            lcs_stride=lcs_stride, lcs_border=lcs_border,
            lcs_patch=lcs_patch,
        )
    else:
        from keystone_tpu.ops.util.nodes import VectorCombiner
        from keystone_tpu.parallel.dataset import Dataset
        from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
            ImageNetSiftLcsFVConfig,
            compute_pca_and_fisher_branch,
            lcs_prefix,
            sift_prefix,
        )
        from keystone_tpu.workflow.api import Pipeline

        if not isinstance(fit_images, Dataset):
            fit_images = Dataset.from_items(
                [np.asarray(x) for x in fit_images]
            )
        conf = ImageNetSiftLcsFVConfig(
            desc_dim=desc_dim, vocab_size=vocab, seed=seed,
            sift_scale_step=sift_scale_step, lcs_stride=lcs_stride,
            lcs_border=lcs_border, lcs_patch=lcs_patch,
        )
        pipe = Pipeline.gather([
            compute_pca_and_fisher_branch(
                sift_prefix(
                    step=sift_step, bin=sift_bin, num_scales=sift_scales,
                    scale_step=sift_scale_step,
                ),
                fit_images, conf, None, None,
            ),
            compute_pca_and_fisher_branch(
                lcs_prefix(lcs_stride, lcs_border, lcs_patch),
                fit_images, conf, None, None,
            ),
        ]).and_then(VectorCombiner())
    fitted = pipe.fit()
    feat_dim = int(
        np.asarray(
            fitted._batch_run(jnp.zeros((1, img, img, 3), jnp.uint8))
        ).shape[-1]
    )
    return fitted, feat_dim


def _hash_update(h, value: Any) -> None:
    """Deterministically fold one operator attribute into the token.
    Arrays hash by shape/dtype/bytes (the weights are the program's
    constants); containers recurse; primitives hash by repr; anything
    else contributes its type name only — weaker, but the parameter
    arrays carry the real identity.

    Every component is FRAMED (type tag + terminator): unframed
    concatenation made distinct parameter sets collide — e.g.
    ``(1, 23)`` and ``(12, 3)`` both fold to the bytes ``123`` — and a
    token collision means two different chains grouped as one."""
    import jax

    if isinstance(value, (np.ndarray, np.generic, jax.Array)):
        arr = np.asarray(value)
        h.update(
            b"a<" + str(arr.shape).encode() + b"|"
            + str(arr.dtype).encode() + b"|"
        )
        h.update(arr.tobytes())
        h.update(b">")
    elif isinstance(value, (str, bytes, int, float, bool, type(None))):
        h.update(b"p<" + repr(value).encode() + b">")
    elif isinstance(value, dict):
        h.update(b"d<")
        for k in sorted(value, key=repr):
            h.update(b"k<" + repr(k).encode() + b">")
            _hash_update(h, value[k])
        h.update(b">")
    elif isinstance(value, (list, tuple)):
        h.update(b"l<")
        for v in value:
            _hash_update(h, v)
        h.update(b">")
    elif dataclasses.is_dataclass(value) and not isinstance(value, type):
        # a node held by another (RowwiseRun's nodes, a Convolver's
        # whitener): its fields are parameters too
        h.update(b"o<" + type(value).__qualname__.encode() + b"|")
        for f in dataclasses.fields(value):
            if not f.name.startswith("_"):
                h.update(b"f<" + f.name.encode() + b">")
                _hash_update(h, getattr(value, f.name, None))
        h.update(b">")
    else:
        h.update(b"t<" + type(value).__qualname__.encode() + b">")


def featurize_token(fitted) -> str:
    """Content digest of a ``FittedPipeline`` — the zoo's CSE grouping
    key (``zoo/cse.py``): operator classes in topological order, the
    graph's wiring, and every operator's attribute values (parameter
    arrays hashed by content). Two chains share a token iff they
    compute the same function, so two pipelines with identical
    architectures but different weights never group together.

    Memoized on the pipeline object (the same lazily-attached-cache
    idiom its operators use): a ``FittedPipeline`` is immutable once
    fit (refits build new objects), so the memo can't go stale."""
    cached = getattr(fitted, "_featurize_token", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for nid in fitted._topo:
        op = fitted.graph.operators[nid]
        # the WIRING is part of the function: same operators in the
        # same topo order compute different things when the edges
        # differ (a Join fed (A(x), x) vs (A(x), A(x))). Ids are hashed
        # by repr — graphs built along different construction paths may
        # token-differ for the same model (a harmless split), but two
        # different computations can never token-collide.
        h.update(
            b"n<" + repr(nid).encode() + b"|"
            + ",".join(
                repr(d) for d in fitted.graph.dependencies[nid]
            ).encode()
            + b">"
        )
        h.update(b"op<" + type(op).__qualname__.encode() + b">")
        if dataclasses.is_dataclass(op):
            # declared fields only: transformers are dataclasses whose
            # fields ARE the parameters
            state = {
                f.name: getattr(op, f.name, None)
                for f in dataclasses.fields(op)
            }
        else:
            state = getattr(op, "__dict__", None) or {}
        for name in sorted(state):
            if name.startswith("_"):
                # lazily-attached caches (_vmapped_apply,
                # _arr_digest_cache, ...) appear after first use; a
                # token that shifted when the pipeline RAN would split
                # one chain into two groups
                continue
            h.update(b"f<" + name.encode() + b">")
            _hash_update(h, state[name])
    h.update(
        b"s<"
        + repr(fitted.graph.sink_dependencies[fitted.sink]).encode()
        + b">"
    )
    token = h.hexdigest()
    try:
        fitted._featurize_token = token
    except Exception:
        pass  # slots/frozen pipeline: just recompute next time
    return token


__all__ = [
    "build_featurize_pipeline",
    "build_flagship_featurize_pipeline",
    "featurize_token",
    "flagship_pipeline",
]
