from keystone_tpu.ops.learning.linear import (
    LinearMapEstimator,
    LinearMapper,
    LocalLeastSquaresEstimator,
    SparseLinearMapper,
)
from keystone_tpu.ops.learning.block_ls import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
)
from keystone_tpu.ops.learning.lbfgs import (
    DenseLBFGSwithL2,
    LeastSquaresDenseGradient,
    LeastSquaresSparseGradient,
    SparseLBFGSwithL2,
)
from keystone_tpu.ops.learning.least_squares import LeastSquaresEstimator
from keystone_tpu.ops.learning.pca import (
    ApproximatePCAEstimator,
    BatchPCATransformer,
    ColumnPCAEstimator,
    DistributedColumnPCAEstimator,
    DistributedPCAEstimator,
    LocalColumnPCAEstimator,
    PCAEstimator,
    PCATransformer,
)
from keystone_tpu.ops.learning.zca import ZCAWhitener, ZCAWhitenerEstimator
from keystone_tpu.ops.learning.kmeans import (
    KMeansModel,
    KMeansPlusPlusEstimator,
)
from keystone_tpu.ops.learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
)
from keystone_tpu.ops.learning.classifiers import (
    LinearDiscriminantAnalysis,
    LogisticRegressionEstimator,
    LogisticRegressionModel,
    NaiveBayesEstimator,
    NaiveBayesModel,
)
from keystone_tpu.ops.learning.weighted_ls import (
    BlockWeightedLeastSquaresEstimator,
    PerClassWeightedLeastSquaresEstimator,
)
from keystone_tpu.ops.learning.kernel import (
    GaussianKernelGenerator,
    GaussianKernelTransformer,
    KernelBlockLinearMapper,
    KernelMatrix,
    KernelRidgeRegression,
)
from keystone_tpu.ops.learning.cost import CostModel
from keystone_tpu.ops.learning.sparse_ell import (
    EllLeastSquaresEstimator,
    EllLinearMapper,
    ell_dataset,
)

__all__ = [
    "ApproximatePCAEstimator",
    "BatchPCATransformer",
    "BlockLeastSquaresEstimator",
    "BlockLinearMapper",
    "BlockWeightedLeastSquaresEstimator",
    "GaussianKernelGenerator",
    "GaussianKernelTransformer",
    "KernelBlockLinearMapper",
    "KernelMatrix",
    "KernelRidgeRegression",
    "PerClassWeightedLeastSquaresEstimator",
    "ColumnPCAEstimator",
    "CostModel",
    "DenseLBFGSwithL2",
    "DistributedColumnPCAEstimator",
    "DistributedPCAEstimator",
    "GaussianMixtureModel",
    "GaussianMixtureModelEstimator",
    "KMeansModel",
    "EllLeastSquaresEstimator",
    "EllLinearMapper",
    "KMeansPlusPlusEstimator",
    "LeastSquaresDenseGradient",
    "ell_dataset",
    "LeastSquaresEstimator",
    "LeastSquaresSparseGradient",
    "LinearDiscriminantAnalysis",
    "LinearMapEstimator",
    "LinearMapper",
    "LocalColumnPCAEstimator",
    "LocalLeastSquaresEstimator",
    "LogisticRegressionEstimator",
    "LogisticRegressionModel",
    "NaiveBayesEstimator",
    "NaiveBayesModel",
    "PCAEstimator",
    "PCATransformer",
    "SparseLBFGSwithL2",
    "SparseLinearMapper",
    "ZCAWhitener",
    "ZCAWhitenerEstimator",
]
