"""End-to-end flagship pipeline on tiny synthetic data (reference:
pipelines/images/imagenet/ImageNetSiftLcsFV.scala), plus a loader test on
a tar in the layout of the reference's test fixture."""

import dataclasses

import numpy as np
import pytest

from keystone_tpu.loaders.image_loaders import (
    ImageExtractor,
    ImageNetLoader,
    LabeledImage,
    LabelExtractor,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
    ImageNetSiftLcsFVConfig,
    run,
)

from jpeg_fixtures import jpeg_array, make_image_tar  # noqa: E402


def test_imagenet_loader_reads_reference_fixture(tmp_path):
    """The reference's fixture is ``n15075141.tar`` of ``{wnid}_{i}.JPEG``
    members beside a labels file of ``wnid class`` lines that maps it to
    12; the same layout from seeded JPEGs, and the decoded pixels against
    the arrays they were encoded from."""
    tar = tmp_path / "n15075141.tar"
    make_image_tar(str(tar), "n15075141", 5, size=(48, 40), seed0=3)
    labels = tmp_path / "imagenet-test-labels"
    labels.write_text("n01000001 3\nn15075141 12\n")
    ds = ImageNetLoader(str(tar), str(labels))
    assert ds.n == 5
    first = ds.first()
    assert first.label == 12
    assert first.image.shape == (40, 48, 3)
    for i, item in enumerate(ds.items()):
        assert item.label == 12
        want = jpeg_array(48, 40, 3 + i).astype(np.float32)
        # quality-92 JPEG of smooth content: a few grey levels
        assert np.abs(np.asarray(item.image) - want).mean() < 4.0


def _synthetic_imagenet(n_per_class=6, num_classes=3, size=48, seed=0):
    rng = np.random.default_rng(seed)
    items = []
    for c in range(num_classes):
        # class-dependent texture frequency so SIFT/LCS carry signal
        freq = 2.0 + 3.0 * c
        for i in range(n_per_class):
            x, y = np.meshgrid(np.arange(size), np.arange(size))
            base = 128 + 100 * np.sin(x / freq) * np.cos(y / freq)
            noise = rng.normal(0, 10, (size, size))
            img = np.stack([base + noise] * 3, axis=-1).clip(0, 255)
            items.append(
                LabeledImage(img.astype(np.float32), c, f"c{c}_{i}")
            )
    return Dataset.from_items(items)


def test_flagship_end_to_end_tiny(mesh8):
    """Proves LEARNING, not just plumbing: 6 classes make top-5 falsifiable
    (a degenerate fixed-5 predictor has top-5 err 1/6) and top-1 must beat
    the best degenerate baseline (5/6 err) by a wide margin. Reference
    accuracy check: ImageNetSiftLcsFV.scala:134-148."""
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=8,
        vocab_size=2,
        lam=1e-4,
        mixture_weight=0.25,
        num_classes=6,
        lcs_stride=8,
        lcs_border=16,
        lcs_patch=6,
        num_pca_samples_per_image=20,
        num_gmm_samples_per_image=20,
    )
    train = _synthetic_imagenet(n_per_class=6, num_classes=6, seed=0)
    test = _synthetic_imagenet(n_per_class=3, num_classes=6, seed=1)
    predictor, err = run(train, test, conf)
    assert err <= 1.0 / 6.0  # beats the degenerate fixed-5-classes baseline

    # top-1: first entry of the top-5 output is the argmax prediction
    test_images = ImageExtractor.apply(test)
    test_labels = np.asarray(LabelExtractor.apply(test).array())
    top5 = np.asarray(predictor(test_images).get().array())
    top1_err = (top5[:, 0] != test_labels).mean()
    assert top1_err <= 0.5  # degenerate single-class baseline is 5/6


def test_flagship_branch_feature_dims(mesh8):
    """Each FV branch must emit 2·descDim·vocabSize features (fv1 ‖ fv2),
    2·2·descDim·vocabSize after the two-branch gather — the num_features
    hint the solver receives (ImageNetSiftLcsFV.scala:139-142)."""
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        compute_pca_and_fisher_branch,
        sift_prefix,
    )

    conf = ImageNetSiftLcsFVConfig(
        desc_dim=8,
        vocab_size=2,
        num_classes=6,
        num_pca_samples_per_image=20,
        num_gmm_samples_per_image=20,
    )
    train = _synthetic_imagenet(n_per_class=3, num_classes=2, seed=0)
    images = ImageExtractor.apply(train)
    branch = compute_pca_and_fisher_branch(
        sift_prefix(scale_step=1), images, conf, None, None
    )
    feats = np.asarray(branch(images).get().array())
    assert feats.shape == (images.n, 2 * conf.desc_dim * conf.vocab_size)


def test_flagship_featurize_jit_batch_matches_executor():
    """FittedPipeline.jit_batch lowers the WHOLE SIFT+LCS -> PCA -> FV
    featurize graph (gather join, bucket-vmapped extractors, Hellinger/
    L2 chain) into one compiled program; it must match the node-by-node
    graph-executor path."""
    import jax.numpy as jnp

    from keystone_tpu.serving.featurize import flagship_pipeline

    rng = np.random.default_rng(0)
    pipe = flagship_pipeline(
        rng, 8, 4, sift_step=8, sift_bin=4, sift_scales=1,
        lcs_stride=8, lcs_border=16, lcs_patch=4,
    )

    imgs = jnp.asarray(
        rng.integers(0, 255, (4, 48, 48, 3)).astype(np.float32)
    )
    ref = pipe.apply(Dataset.from_array(imgs)).get().padded()
    out = pipe.fit().jit_batch()(imgs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


# Linearised graph of ``flagship_pipeline`` at the benchmark's parameters
# (``benchmark/configs/imagenet-sift-lcs-fv.json``), recorded at commit
# 7243142: label, the type names nested in ``eq_key()``, dependencies.
_STR = ["str"]
_NORMALIZE = ["type", [["str", "float"]]]
_PCA = ["type", [["str", ["str", ["int", "int"], "str", "str"]]]]
_FV = ["type", [["str", "GaussianMixtureModel"]]]
_INT_FIELD = ["str", "int"]
FLAGSHIP_GRAPH_AT_7243142 = [
    ("PixelScaler", _STR, ["source0"]),
    ("GrayScaler", _STR, ["node0"]),
    ("SIFTExtractor", ["type", [_INT_FIELD] * 4], ["node1"]),
    ("SignedHellingerMapper", _STR, ["node2"]),
    ("BatchPCATransformer", _PCA, ["node3"]),
    ("FisherVector", _FV, ["node4"]),
    ("FloatToDouble", _STR, ["node5"]),
    ("MatrixVectorizer", _STR, ["node6"]),
    ("NormalizeRows", _NORMALIZE, ["node7"]),
    ("SignedHellingerMapper", _STR, ["node8"]),
    ("NormalizeRows", _NORMALIZE, ["node9"]),
    ("LCSExtractor", ["type", [_INT_FIELD] * 3], ["source0"]),
    ("BatchPCATransformer", _PCA, ["node11"]),
    ("FisherVector", _FV, ["node12"]),
    ("FloatToDouble", _STR, ["node13"]),
    ("MatrixVectorizer", _STR, ["node14"]),
    ("NormalizeRows", _NORMALIZE, ["node15"]),
    ("SignedHellingerMapper", _STR, ["node16"]),
    ("NormalizeRows", _NORMALIZE, ["node17"]),
    ("gather", _STR, ["node10", "node18"]),
    ("VectorCombiner", _STR, ["node19"]),
]
# The same seeded pipeline's token. It once read 8c0df507…: a node held
# by another node (FisherVector's GaussianMixtureModel) gave the token its
# type name and nothing of its arrays, so two flagships that differed in
# their GMMs alone shared a token; the token now folds a held node's
# fields in (a merged RowwiseRun's nodes need it), which moved this value
# once and the graph above not at all.
FLAGSHIP_TOKEN_AT_7243142 = (
    "d8344da50ba0b95752766b95380831d904994d40ee69f731ff22c1a14ae850cd"
)


def _type_names(value):
    if isinstance(value, tuple):
        return [_type_names(v) for v in value]
    return type(value).__name__


def test_flagship_pipeline_graph_is_what_it_was():
    """``flagship_pipeline`` (the one ``flagship-score`` runs) and the
    application share ``sift_prefix`` / ``lcs_prefix`` / ``fisher_branch``;
    the seeded pipeline's nodes, their order, their wiring and its
    parameters (the content token) are what they were when it spelt
    the dataflow itself: the same XLA programs, 59 dispatches a step."""
    from keystone_tpu.serving.featurize import (
        featurize_token,
        flagship_pipeline,
    )
    from keystone_tpu.workflow.graph import NodeId, linearize

    pipe = flagship_pipeline(
        np.random.default_rng(0), 64, 16,
        sift_step=3, sift_bin=4, sift_scales=4, sift_scale_step=1,
        lcs_stride=4, lcs_border=16, lcs_patch=6,
    )
    graph = pipe._graph
    got = []
    for gid in linearize(graph):
        if isinstance(gid, NodeId):
            op = graph.get_operator(gid)
            got.append((
                op.label, _type_names(op.eq_key()),
                [str(d) for d in graph.get_dependencies(gid)],
            ))
    assert got == FLAGSHIP_GRAPH_AT_7243142
    assert featurize_token(pipe.fit()) == FLAGSHIP_TOKEN_AT_7243142
    # and a held node's arrays count: another GMM, another token
    other = pipe.fit()
    fv = next(op for op in other.graph.operators.values()
              if type(op).__name__ == "FisherVector")
    fv.gmm = dataclasses.replace(fv.gmm, means=fv.gmm.means + 1.0)
    assert featurize_token(other) != FLAGSHIP_TOKEN_AT_7243142


def test_flagship_features_separate_textures_and_keep_their_rank():
    """Featurize health on the seeded pipeline: images of four textures
    land in feature clusters further apart than they are wide, and the
    within-cluster deviations (per-image noise) excite several feature
    directions. Collapsed or constant features fail the first, a
    rank-one featurizer the second (it reads 1; healthy reads ~4 here)."""
    from keystone_tpu.serving.featurize import flagship_pipeline

    items = _synthetic_imagenet(n_per_class=8, num_classes=4, size=64).items()
    ids = np.asarray([li.label for li in items])
    pipe = flagship_pipeline(
        np.random.default_rng(3), 8, 4, sift_step=4, sift_scales=2
    )
    feats = np.asarray(
        pipe(Dataset.from_items(
            [li.image.astype(np.uint8) for li in items]
        )).get().array(),
        np.float64,
    )
    assert feats.shape == (32, 2 * 2 * 8 * 4)
    cents = np.stack([feats[ids == c].mean(0) for c in range(4)])
    within = np.mean([
        np.linalg.norm(feats[ids == c] - cents[c], axis=1).mean()
        for c in range(4)
    ])
    between = np.linalg.norm(cents[:, None] - cents[None], axis=2)
    assert between[~np.eye(4, dtype=bool)].min() > 2.0 * within
    sv = np.linalg.svd(feats - cents[ids], compute_uv=False)
    assert (sv ** 2).sum() / sv[0] ** 2 > 2.0
