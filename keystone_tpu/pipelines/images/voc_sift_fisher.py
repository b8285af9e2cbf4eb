"""VOCSIFTFisher — SIFT -> PCA -> Fisher Vectors -> BlockLS, evaluated by
VOC mean average precision.

Reference: pipelines/images/voc/VOCSIFTFisher.scala:23-110.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from keystone_tpu.evaluation import MeanAveragePrecisionEvaluator
from keystone_tpu.loaders.image_loaders import (
    MultiLabelExtractor,
    VOCLoader,
)
from keystone_tpu.ops.images.fisher_vector import (
    FisherVector,
    GMMFisherVectorEstimator,
)
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
from keystone_tpu.ops.learning import (
    BatchPCATransformer,
    BlockLeastSquaresEstimator,
    ColumnPCAEstimator,
)
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel
from keystone_tpu.ops.stats import (
    ColumnSampler,
    NormalizeRows,
    SignedHellingerMapper,
)
from keystone_tpu.ops.util.cacher import Cacher
from keystone_tpu.ops.util.nodes import (
    ClassLabelIndicatorsFromIntArrayLabels,
    MatrixVectorizer,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Pipeline

NUM_VOC_CLASSES = 20


@dataclasses.dataclass
class SIFTFisherConfig:
    train_location: str = ""
    test_location: str = ""
    label_path: str = ""
    lam: float = 0.5
    desc_dim: int = 80
    vocab_size: int = 256
    scale_step: int = 0
    # descriptors sampled for the PCA and for the GMM over the whole
    # training set (VOCSIFTFisher.scala: numPcaSamples, numGmmSamples)
    num_pca_samples: int = 1_000_000
    num_gmm_samples: int = 1_000_000
    num_classes: int = NUM_VOC_CLASSES
    seed: int = 0
    pca_file: Optional[str] = None
    gmm_files: Optional[tuple] = None


def samples_per_image(num_samples: int, num_images: int) -> int:
    """Columns ``ColumnSampler`` draws of each image's descriptors: the
    Scala file's ``numSamples / numImgs`` (integer division), at least 1."""
    return max(num_samples // max(num_images, 1), 1)


def build_pipeline(
    training_data: Dataset, training_labels, conf: SIFTFisherConfig
) -> Pipeline:
    num_images = Dataset.of(training_data).n
    sift_extractor = (
        PixelScaler()
        .and_then(GrayScaler())
        .and_then(Cacher())
        .and_then(SIFTExtractor(scale_step=conf.scale_step))
    )

    if conf.pca_file is not None:
        pca_mat = np.loadtxt(conf.pca_file, delimiter=",").astype(np.float32)
        pca_featurizer = sift_extractor.and_then(
            BatchPCATransformer(jnp.asarray(pca_mat).T)
        )
    else:
        sampled = ColumnSampler(
            samples_per_image(conf.num_pca_samples, num_images),
            seed=conf.seed,
        )(sift_extractor(training_data))
        pca = ColumnPCAEstimator(conf.desc_dim).with_data(sampled)
        pca_featurizer = sift_extractor.and_then(pca)
    pca_featurizer = pca_featurizer.and_then(Cacher())

    if conf.gmm_files is not None:
        gmm = GaussianMixtureModel.load(*conf.gmm_files)
        fisher_featurizer = pca_featurizer.and_then(FisherVector(gmm))
    else:
        sampled = ColumnSampler(
            samples_per_image(conf.num_gmm_samples, num_images),
            seed=conf.seed + 1,
        )(pca_featurizer(training_data))
        fv = GMMFisherVectorEstimator(
            conf.vocab_size, seed=conf.seed
        ).with_data(sampled)
        fisher_featurizer = pca_featurizer.and_then(fv)

    fisher_featurizer = (
        # (the reference's FloatToDouble stood here: a cast to the driver's
        # float64, which on this float32 path changed nothing)
        fisher_featurizer.and_then(MatrixVectorizer())
        .and_then(NormalizeRows())
        .and_then(SignedHellingerMapper())
        .and_then(NormalizeRows())
        .and_then(Cacher())
    )

    return fisher_featurizer.and_then(
        BlockLeastSquaresEstimator(
            4096, 1, conf.lam,
            num_features=2 * conf.desc_dim * conf.vocab_size,
        ),
        training_data,
        training_labels,
    )


def run(train_data: Dataset, test_data: Dataset, conf: SIFTFisherConfig):
    training_images = train_data.map(lambda li: li.image)
    label_grabber = ClassLabelIndicatorsFromIntArrayLabels(conf.num_classes)
    training_labels = label_grabber.apply_batch(
        MultiLabelExtractor.apply(train_data)
    )
    predictor = build_pipeline(training_images, training_labels, conf)

    test_images = test_data.map(lambda li: li.image)
    test_actuals = MultiLabelExtractor.apply(test_data).items()
    predictions = predictor(test_images).get()
    aps = MeanAveragePrecisionEvaluator(conf.num_classes).evaluate(
        test_actuals, predictions
    )
    return predictor, float(np.mean(aps))


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(description="VOCSIFTFisher")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--labelPath", required=True)
    p.add_argument("--lambda", dest="lam", type=float, default=0.5)
    p.add_argument("--descDim", type=int, default=80)
    p.add_argument("--vocabSize", type=int, default=256)
    p.add_argument("--scaleStep", type=int, default=0)
    p.add_argument("--numPcaSamples", type=int, default=1_000_000)
    p.add_argument("--numGmmSamples", type=int, default=1_000_000)
    a = p.parse_args(argv)
    conf = SIFTFisherConfig(
        a.trainLocation, a.testLocation, a.labelPath, a.lam, a.descDim,
        a.vocabSize, a.scaleStep, a.numPcaSamples, a.numGmmSamples,
    )
    train = VOCLoader(conf.train_location, conf.label_path)
    test = VOCLoader(conf.test_location, conf.label_path)
    t0 = time.time()
    _, mean_ap = run(train, test, conf)
    print(f"TEST MAP is: {mean_ap:.4f}")
    print(f"Total time: {time.time() - t0:.2f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
