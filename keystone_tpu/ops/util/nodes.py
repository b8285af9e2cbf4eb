"""Representation/utility nodes.

Reference: nodes/util/*.scala — VectorSplitter, ClassLabelIndicators,
CommonSparseFeatures/AllSparseFeatures/SparseFeatureVectorizer,
MaxClassifier/TopKClassifier, Densify/Sparsify/FloatToDouble/
MatrixVectorizer/VectorCombiner/Shuffler.

Sparse data uses jax.experimental.sparse.BCOO so sparse models still run as
XLA programs on the MXU-adjacent hardware rather than host loops.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Estimator, FunctionNode, Transformer


class VectorSplitter(FunctionNode):
    """Split a dataset of feature vectors into feature-dimension blocks —
    the primitive behind all block solvers (reference:
    nodes/util/VectorSplitter.scala). Returns a list of Datasets, one per
    block; the last block may be narrower."""

    def __init__(self, block_size: int, num_features: int = None):
        self.block_size = block_size
        self.num_features = num_features

    def apply(self, data: Any) -> List[Dataset]:
        ds = Dataset.of(data if isinstance(data, Dataset) else data)
        x = ds.padded()
        d = self.num_features or x.shape[1]
        blocks = []
        for start in range(0, d, self.block_size):
            end = min(start + self.block_size, d)
            blocks.append(Dataset.from_array(x[:, start:end], n=ds.n))
        return blocks


@dataclasses.dataclass(eq=False)
class ClassLabelIndicators(Transformer):
    """int label -> ±1 indicator vector (reference:
    nodes/util/ClassLabelIndicators.scala:15)."""

    num_classes: int

    def apply(self, y):
        return 2.0 * jax.nn.one_hot(y, self.num_classes) - 1.0

    def apply_batch(self, ds: Dataset) -> Dataset:
        y = ds.padded().astype(jnp.int32)
        out = 2.0 * jax.nn.one_hot(y, self.num_classes) - 1.0
        # one-hot of zero pad rows is (+1,-1,...): keep pad rows zero
        out = out * ds.mask()[:, None]
        return Dataset.from_array(out, n=ds.n)


@dataclasses.dataclass(eq=False)
class ClassLabelIndicatorsFromIntArrayLabels(Transformer):
    """multi-label int array -> ±1 indicator vector."""

    num_classes: int
    vmap_batch = False

    def apply(self, ys):
        base = -np.ones(self.num_classes, dtype=np.float32)
        base[np.asarray(ys, dtype=np.int64)] = 1.0
        return jnp.asarray(base)


class MaxClassifier(Transformer):
    """argmax over scores (reference: nodes/util/MaxClassifier.scala)."""

    def apply(self, scores):
        return jnp.argmax(scores, axis=-1)

    def apply_batch(self, ds: Dataset) -> Dataset:
        return Dataset.from_array(
            jnp.argmax(ds.padded(), axis=-1), n=ds.n
        )

    def eq_key(self):
        return ("max_classifier",)


@dataclasses.dataclass(eq=False)
class TopKClassifier(Transformer):
    """top-k class indices, best first (reference: TopKClassifier.scala)."""

    k: int

    def apply(self, scores):
        _, idx = jax.lax.top_k(scores, min(self.k, scores.shape[-1]))
        return idx

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        _, idx = jax.lax.top_k(x, min(self.k, x.shape[-1]))
        return Dataset.from_array(idx, n=ds.n)


class VectorCombiner(Transformer):
    """Concatenate gathered branch outputs along the feature axis
    (reference: nodes/util/VectorCombiner.scala)."""

    def apply(self, parts):
        return jnp.concatenate([jnp.ravel(p) for p in parts], axis=0)

    def apply_batch(self, ds: Dataset) -> Dataset:
        arrs = ds.padded()
        if isinstance(arrs, tuple):
            flat = [a.reshape(a.shape[0], -1) for a in arrs]
            return Dataset.from_array(jnp.concatenate(flat, axis=1), n=ds.n)
        return ds.map(self.apply)

    def eq_key(self):
        return ("vector_combiner",)


class MatrixVectorizer(Transformer):
    """Flatten a matrix datum into a vector (column-major, matching Breeze's
    DenseMatrix.toDenseVector semantics in the reference)."""

    def apply(self, m):
        return jnp.ravel(m, order="F")

    def rowwise(self):
        return _vectorize_matrices, ()

    def eq_key(self):
        return ("matrix_vectorizer",)


def _vectorize_matrices(arrays, x):
    """MatrixVectorizer's rows-in, rows-out function: (n, r, c) matrices
    to (n, r c) column-major vectors."""
    del arrays
    return jnp.swapaxes(x, 1, 2).reshape(x.shape[0], -1)


_vectorize_matrices.groups_only = True


class FloatToDouble(Transformer):
    """The reference's cast to the driver's float64. With jax's x64 off
    (the default, and every pipeline here) there is no float64: the node
    casts to float32, which on a float32 path is the identity."""

    def apply(self, x):
        return x.astype(jnp.float64) if jax.config.jax_enable_x64 else x.astype(jnp.float32)

    def eq_key(self):
        return ("float_to_double",)


class Densify(Transformer):
    """Sparse BCOO -> dense."""

    vmap_batch = False

    def apply(self, x):
        return x.todense() if isinstance(x, jsparse.BCOO) else jnp.asarray(x)

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            arrs = ds.padded()
            if isinstance(arrs, jsparse.BCOO):
                return Dataset.from_array(arrs.todense(), n=ds.n)
            return ds
        return ds.map(self.apply)

    def eq_key(self):
        return ("densify",)


class Sparsify(Transformer):
    """Dense -> sparse BCOO batch."""

    vmap_batch = False

    def apply(self, x):
        return jsparse.BCOO.fromdense(jnp.asarray(x))

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.to_array_mode().padded()
        return Dataset.from_array(jsparse.BCOO.fromdense(x), n=ds.n)

    def eq_key(self):
        return ("sparsify",)


class Shuffler(Transformer):
    """Random permutation of examples (reference: repartition-based
    Shuffler). ``device=True`` routes rows through one ``lax.all_to_all``
    over the mesh's data axis (parallel/shuffle.py) — the shuffle never
    leaves the devices; the default host path materializes and permutes
    (bit-identical results either way)."""

    def __init__(self, seed: int = 0, device: bool = False):
        self.seed = seed
        self.device = device

    def apply(self, x):
        return x

    def apply_batch(self, ds: Dataset) -> Dataset:
        if (
            self.device
            and ds.is_array
            and not isinstance(ds.padded(), tuple)
        ):
            from keystone_tpu.parallel import mesh as mesh_lib
            from keystone_tpu.parallel.shuffle import device_shuffle

            mesh = mesh_lib.current_mesh()
            x = ds.padded()
            if x.shape[0] % mesh_lib.n_data_shards(mesh) == 0:
                return Dataset.from_array(
                    device_shuffle(x, ds.n, self.seed, mesh), n=ds.n
                )
            import logging

            logging.getLogger(__name__).warning(
                "Shuffler(device=True): %d padded rows not divisible by "
                "%d data shards; falling back to the host path (full "
                "array materializes on host)",
                x.shape[0], mesh_lib.n_data_shards(mesh),
            )
        rng = np.random.default_rng(self.seed)
        perm = rng.permutation(ds.n)
        if ds.is_array and not isinstance(ds.padded(), tuple):
            x = ds.array()
            return Dataset.from_array(jnp.asarray(np.asarray(x))[perm], n=ds.n)
        items = ds.items()
        return Dataset.from_items([items[i] for i in perm])


# -- sparse feature space estimators ---------------------------------------


@dataclasses.dataclass(eq=False)
class SparseFeatureVectorizer(Transformer):
    """term-count dict -> BCOO sparse vector given a feature->index map
    (reference: nodes/util/SparseFeatureVectorizer.scala)."""

    feature_index: dict
    dim: int
    vmap_batch = False

    def apply(self, counts: dict):
        idx, vals = [], []
        for k, v in counts.items():
            j = self.feature_index.get(k)
            if j is not None:
                idx.append(j)
                vals.append(v)
        order = np.argsort(idx) if idx else []
        indices = np.asarray(idx, dtype=np.int32)[order].reshape(-1, 1)
        values = np.asarray(vals, dtype=np.float32)[order]
        return jsparse.BCOO(
            (jnp.asarray(values), jnp.asarray(indices)), shape=(self.dim,)
        )

    def apply_batch(self, ds: Dataset) -> Dataset:
        """Batch to one (n, dim) BCOO matrix."""
        rows, cols, vals = [], [], []
        items = ds.items()
        for i, counts in enumerate(items):
            for k, v in counts.items():
                j = self.feature_index.get(k)
                if j is not None:
                    rows.append(i)
                    cols.append(j)
                    vals.append(v)
        indices = jnp.asarray(
            np.stack(
                [np.asarray(rows, np.int32), np.asarray(cols, np.int32)],
                axis=1,
            )
            if rows
            else np.zeros((0, 2), np.int32)
        )
        values = jnp.asarray(np.asarray(vals, np.float32))
        mat = jsparse.BCOO(
            (values, indices), shape=(len(items), self.dim)
        )
        return Dataset.from_array(mat, n=len(items))

    def eq_key(self):
        return ("sparse_vectorizer", self.dim, id(self.feature_index))


@dataclasses.dataclass(eq=False)
class CommonSparseFeatures(Estimator):
    """Keep the top-k most frequent features (reference:
    nodes/util/CommonSparseFeatures.scala — per-partition takeOrdered +
    treeReduce merge; here a host Counter over the training sample)."""

    num_features: int

    def fit(self, data: Dataset) -> SparseFeatureVectorizer:
        counts: Counter = Counter()
        for item in data.items():
            # every occurrence counts once, value included-but-ignored —
            # CommonSparseFeatures.scala:37 flatMaps all (feature, value)
            # pairs with weight 1 regardless of the value
            counts.update(item.keys())
        top = [k for k, _ in counts.most_common(self.num_features)]
        index = {k: i for i, k in enumerate(top)}
        return SparseFeatureVectorizer(index, self.num_features)


@dataclasses.dataclass(eq=False)
class AllSparseFeatures(Estimator):
    """Keep every observed feature, deterministically ordered (reference:
    nodes/util/AllSparseFeatures.scala)."""

    def fit(self, data: Dataset) -> SparseFeatureVectorizer:
        seen = set()
        for item in data.items():
            seen.update(item.keys())
        ordered = sorted(seen, key=lambda k: str(k))
        index = {k: i for i, k in enumerate(ordered)}
        return SparseFeatureVectorizer(index, len(ordered))
