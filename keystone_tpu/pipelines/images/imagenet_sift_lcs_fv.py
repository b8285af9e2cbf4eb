"""ImageNetSiftLcsFV — the flagship pipeline: SIFT + LCS branches, each
PCA -> GMM Fisher Vectors -> normalization, gathered and fed to the
mixture-weighted block least-squares solver, Top-5 output.

Reference: pipelines/images/imagenet/ImageNetSiftLcsFV.scala:29-151.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from keystone_tpu.loaders.image_loaders import (
    ImageExtractor,
    ImageNetLoader,
    LabelExtractor,
    NUM_IMAGENET_CLASSES,
)
from keystone_tpu.ops.images.fisher_vector import GMMFisherVectorEstimator
from keystone_tpu.ops.images.lcs import LCSExtractor
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
from keystone_tpu.ops.learning import BatchPCATransformer, ColumnPCAEstimator
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
from keystone_tpu.ops.learning.weighted_ls import (
    BlockWeightedLeastSquaresEstimator,
)
from keystone_tpu.ops.stats import (
    ColumnSampler,
    NormalizeRows,
    SignedHellingerMapper,
)
from keystone_tpu.ops.util.cacher import Cacher
from keystone_tpu.ops.util.nodes import (
    ClassLabelIndicators,
    FloatToDouble,
    MatrixVectorizer,
    TopKClassifier,
    VectorCombiner,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Pipeline


@dataclasses.dataclass
class ImageNetSiftLcsFVConfig:
    train_location: str = ""
    test_location: str = ""
    label_path: str = ""
    lam: float = 6e-5
    mixture_weight: float = 0.25
    desc_dim: int = 64
    vocab_size: int = 16
    sift_scale_step: int = 1
    lcs_stride: int = 4
    lcs_border: int = 16
    lcs_patch: int = 6
    num_pca_samples_per_image: int = 10
    num_gmm_samples_per_image: int = 10
    num_classes: int = NUM_IMAGENET_CLASSES
    seed: int = 0
    # optional warm-start files (reference: pcaFile/gmmMeanFile/...)
    sift_pca_file: Optional[str] = None
    sift_gmm_files: Optional[tuple] = None  # (means, vars, weights)
    lcs_pca_file: Optional[str] = None
    lcs_gmm_files: Optional[tuple] = None


def sift_prefix(**sift) -> Pipeline:
    """Pixels to Hellinger-mapped dense SIFT descriptors; ``sift`` goes
    to ``SIFTExtractor`` (reference: ImageNetSiftLcsFV.scala:106-110)."""
    return (
        PixelScaler()
        .and_then(GrayScaler())
        .and_then(SIFTExtractor(**sift))
        .and_then(SignedHellingerMapper())
    )


def lcs_prefix(stride: int, border: int, patch: int) -> Pipeline:
    """Pixels to LCS descriptors (reference: ImageNetSiftLcsFV.scala:120)."""
    return LCSExtractor(stride, border, patch).to_pipeline()


def fisher_branch(prefix: Pipeline, pca_pipeline, fv_pipeline) -> Pipeline:
    """One branch of the flagship's featurizer: descriptors → PCA →
    Fisher vector → one L2-normalised, Hellinger-mapped row an image.
    The application hands it fitted estimators, the seeded
    ``serving/featurize.py: flagship_pipeline`` transformers."""
    return (
        prefix.and_then(pca_pipeline)
        .and_then(fv_pipeline)
        .and_then(FloatToDouble())
        .and_then(MatrixVectorizer())
        .and_then(NormalizeRows())
        .and_then(SignedHellingerMapper())
        .and_then(NormalizeRows())
    )


def compute_pca_and_fisher_branch(
    prefix: Pipeline,
    training_data,
    conf: ImageNetSiftLcsFVConfig,
    pca_file: Optional[str],
    gmm_files: Optional[tuple],
) -> Pipeline:
    """reference: ImageNetSiftLcsFV.computePCAandFisherBranch:29-80."""
    if pca_file is not None:
        pca_mat = np.loadtxt(pca_file, delimiter=",").astype(np.float32)
        pca_pipeline = BatchPCATransformer(jnp.asarray(pca_mat).T).to_pipeline()
    else:
        sampled = ColumnSampler(
            conf.num_pca_samples_per_image, seed=conf.seed
        )(prefix(training_data))
        pca_pipeline = ColumnPCAEstimator(conf.desc_dim).with_data(sampled)

    if gmm_files is not None:
        gmm = GaussianMixtureModel.load(*gmm_files)
        from keystone_tpu.ops.images.fisher_vector import FisherVector

        fv_pipeline = FisherVector(gmm).to_pipeline()
    else:
        sampled = ColumnSampler(
            conf.num_gmm_samples_per_image, seed=conf.seed + 1
        )(prefix(training_data))
        fv_pipeline = GMMFisherVectorEstimator(
            conf.vocab_size, seed=conf.seed
        ).with_data(pca_pipeline.apply(sampled))

    return fisher_branch(prefix, pca_pipeline, fv_pipeline)


def fit_classifier(
    featurizer, train_data, train_labels, conf: ImageNetSiftLcsFVConfig
) -> Pipeline:
    """The fit from Fisher vectors to model (reference:
    ImageNetSiftLcsFV.scala:136-143): ``featurizer`` → Cacher → the
    mixture-weighted block solver at the reference's settings (block
    4096, one pass, ``conf.lam``, ``conf.mixture_weight``), fitted on
    ``featurizer(train_data)`` and the ±1 indicators of the integer
    ``train_labels`` → TopKClassifier(5). ``build_pipeline`` ends in it;
    a caller that already holds the combined Fisher vectors passes them
    as ``train_data`` with ``Identity()`` as the featurizer."""
    indicator_labels = ClassLabelIndicators(conf.num_classes)(train_labels)
    num_features = 2 * 2 * conf.desc_dim * conf.vocab_size
    return (
        featurizer.and_then(Cacher())
        .and_then(
            BlockWeightedLeastSquaresEstimator(
                4096, 1, conf.lam, conf.mixture_weight,
                num_features=num_features,
            ),
            train_data,
            indicator_labels,
        )
        .and_then(TopKClassifier(5))
    )


def build_pipeline(
    train_images: Dataset, train_labels, conf: ImageNetSiftLcsFVConfig
) -> Pipeline:
    sift_branch = compute_pca_and_fisher_branch(
        sift_prefix(scale_step=conf.sift_scale_step), train_images, conf,
        conf.sift_pca_file, conf.sift_gmm_files,
    )
    lcs_branch = compute_pca_and_fisher_branch(
        lcs_prefix(conf.lcs_stride, conf.lcs_border, conf.lcs_patch),
        train_images, conf, conf.lcs_pca_file, conf.lcs_gmm_files,
    )

    featurizer = Pipeline.gather([sift_branch, lcs_branch]).and_then(
        VectorCombiner()
    )
    return fit_classifier(featurizer, train_images, train_labels, conf)


def run(train_data: Dataset, test_data: Dataset, conf: ImageNetSiftLcsFVConfig):
    train_images = ImageExtractor.apply(train_data)
    train_labels = LabelExtractor.apply(train_data)
    test_images = ImageExtractor.apply(test_data)
    test_labels = LabelExtractor.apply(test_data)

    predictor = build_pipeline(train_images, train_labels, conf)
    predicted = predictor(test_images).get()
    top5 = np.asarray(predicted.array())
    actual = np.asarray(test_labels.array())
    err = 1.0 - np.mean([a in p for a, p in zip(actual, top5)])
    return predictor, err


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description="ImageNetSiftLcsFV")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--labelPath", required=True)
    p.add_argument("--lambda", dest="lam", type=float, default=6e-5)
    p.add_argument("--mixtureWeight", type=float, default=0.25)
    p.add_argument("--descDim", type=int, default=64)
    p.add_argument("--vocabSize", type=int, default=16)
    p.add_argument("--siftScaleStep", type=int, default=1)
    a = p.parse_args(argv)
    conf = ImageNetSiftLcsFVConfig(
        a.trainLocation, a.testLocation, a.labelPath, a.lam,
        a.mixtureWeight, a.descDim, a.vocabSize, a.siftScaleStep,
    )
    train = ImageNetLoader(conf.train_location, conf.label_path)
    test = ImageNetLoader(conf.test_location, conf.label_path)
    t0 = time.time()
    _, err = run(train, test, conf)
    print(f"TEST Top-5 error is {100 * err:.2f}%")
    print(f"Total time: {time.time() - t0:.2f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
