"""Statistical feature nodes.

Reference: nodes/stats/*.scala — CosineRandomFeatures, PaddedFFT,
StandardScaler, LinearRectifier, RandomSignNode, NormalizeRows,
SignedHellingerMapper, TermFrequency, Sampling.

TPU-first notes: every batch path is one fused jnp expression over the
sharded (n, d) matrix — XLA maps the matmuls onto the MXU and fuses the
elementwise tails; reductions over the example axis turn into psums over the
mesh's data axis automatically under jit.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from functools import lru_cache, partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import Estimator, FunctionNode, Transformer


@dataclasses.dataclass(eq=False)
class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed ±1 sign vector (reference:
    nodes/stats/RandomSignNode.scala:10; factory draws Binomial signs)."""

    signs: Any  # (d,) array of ±1

    @staticmethod
    def create(d: int, seed: int = 0) -> "RandomSignNode":
        rng = np.random.default_rng(seed)
        signs = rng.integers(0, 2, size=d).astype(np.float32) * 2.0 - 1.0
        return RandomSignNode(jnp.asarray(signs))

    def apply(self, x):
        return x * self.signs

    def apply_batch(self, ds: Dataset) -> Dataset:
        return Dataset.from_array(ds.padded() * self.signs, n=ds.n)


@dataclasses.dataclass(eq=False)
class PaddedFFT(Transformer):
    """Zero-pad to the next power of two, real FFT, keep the real parts of
    the first half (reference: nodes/stats/PaddedFFT.scala:13 — Breeze
    fourierTr then x(0 until pad/2).map(_.real))."""

    def _pad_len(self, d: int) -> int:
        return int(2 ** np.ceil(np.log2(max(d, 1))))

    def apply(self, x):
        pad = self._pad_len(x.shape[-1])
        xp = jnp.zeros(pad, x.dtype).at[: x.shape[-1]].set(x)
        return jnp.real(jnp.fft.fft(xp))[: pad // 2]

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        pad = self._pad_len(x.shape[-1])
        xp = jnp.pad(x, ((0, 0), (0, pad - x.shape[-1])))
        return Dataset.from_array(
            jnp.real(jnp.fft.fft(xp, axis=-1))[:, : pad // 2], n=ds.n
        )

    def eq_key(self):
        return ("padded_fft",)


_HIGHEST = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("thresh",))
def _fft_bank(x, signs, cos, mask, *, thresh: float):
    """All branches of RandomFFTFeatures as ONE product — module level
    so the jit cache is shared across instances and calls: rectify(x·B),
    where column f·(pad/2) + k of B is signs[f, j]·cos(2π·j·k / pad),
    gives the real parts of the first pad/2 coefficients of each signed,
    zero-padded row's DFT, in float32 at HIGHEST on the MXU. The output
    is the product's own (rows, branches·pad/2) array; B, (d,
    branches·pad/2), is its one temporary. ``mask`` re-zeroes pad rows
    when thresh > 0 would lift them."""
    d = cos.shape[0]
    with jax.named_scope("fft.bank"):
        basis = (signs.T[:, :, None] * cos[:, None, :]).reshape(d, -1)
        out = jnp.maximum(jnp.matmul(x, basis, precision=_HIGHEST), thresh)
        if thresh > 0:
            out = out * mask[:, None]
        return out


@lru_cache(maxsize=8)
def _cosines(d: int, pad: int) -> np.ndarray:
    """(d, pad / 2) float32 cos(2π·j·k / pad), the angle reduced
    modulo pad in integers and the cosine taken in float64."""
    jk = (np.arange(d)[:, None] * np.arange(pad // 2)[None, :]) % pad
    return np.cos(2.0 * np.pi * jk / pad).astype(np.float32)


@dataclasses.dataclass(eq=False)
class RandomFFTFeatures(Transformer):
    """All ``num_ffts`` random-sign -> PaddedFFT -> rectify branches of
    the MnistRandomFFT featurization in ONE jitted program (reference
    composes per-branch pipelines, MnistRandomFFT.scala:28-37; the math
    is identical — this is the batched physical plan: the kept half of
    each branch's padded DFT as one product with a signed cosine basis
    on the MXU, instead of 3 x num_ffts separate dispatches + a
    concatenate; one row takes the product of its signed copies with the
    cosines, and no basis is formed). Span ``fft.bank`` (the batch
    path's dispatch), scope ``fft.bank``, counter
    ``keystone_featurize_fft_rows_total``."""

    signs: Any  # (num_ffts, d)
    rectify_threshold: float = 0.0

    @staticmethod
    def create(
        d: int, num_ffts: int, seed: int = 0, rectify_threshold: float = 0.0
    ) -> "RandomFFTFeatures":
        """Branch i's signs match ``RandomSignNode.create(d, seed + i)``,
        so the fused node is numerically interchangeable with the
        composed per-branch pipelines."""
        signs = np.stack([
            np.random.default_rng(seed + i)
            .integers(0, 2, size=d)
            .astype(np.float32) * 2.0 - 1.0
            for i in range(num_ffts)
        ])
        return RandomFFTFeatures(
            jnp.asarray(signs), rectify_threshold=rectify_threshold
        )

    def _pad_len(self, d: int) -> int:
        return int(2 ** np.ceil(np.log2(max(d, 1))))

    @property
    def out_dim(self) -> int:
        return self.signs.shape[0] * (self._pad_len(self.signs.shape[1]) // 2)

    def _cos(self, d: int) -> np.ndarray:
        return _cosines(d, self._pad_len(d))

    def apply(self, x):
        spec = jnp.matmul(x[None, :] * self.signs, self._cos(x.shape[-1]),
                          precision=_HIGHEST)
        return jnp.maximum(spec, self.rectify_threshold).reshape(-1)

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        with span("fft.bank", n=ds.n, branches=self.signs.shape[0]):
            out = _fft_bank(
                x, self.signs, self._cos(x.shape[-1]), ds.mask(),
                thresh=float(self.rectify_threshold),
            )
        get_global_registry().counter(
            "keystone_featurize_fft_rows_total",
            "rows through RandomFFTFeatures' batch path",
        ).inc(by=ds.n)
        return Dataset.from_array(out, n=ds.n)


@dataclasses.dataclass(eq=False)
class LinearRectifier(Transformer):
    """max(max_val, x - alpha) (reference:
    nodes/stats/LinearRectifier.scala:12)."""

    max_val: float = 0.0
    alpha: float = 0.0

    def apply(self, x):
        return jnp.maximum(self.max_val, x - self.alpha)

    def apply_batch(self, ds: Dataset) -> Dataset:
        out = jnp.maximum(self.max_val, ds.padded() - self.alpha)
        if self.max_val > 0 or self.alpha < 0:
            # rectified zero pad rows would be nonzero: keep the invariant
            out = out * ds.mask()[:, None]
        return Dataset.from_array(out, n=ds.n)


@dataclasses.dataclass(eq=False)
class NormalizeRows(Transformer):
    """L2 row normalization with a tiny-norm floor (reference:
    nodes/stats/NormalizeRows.scala:10, floor 2.2e-16)."""

    floor: float = 2.2e-16

    def apply(self, x):
        nrm = jnp.linalg.norm(x)
        return x / jnp.maximum(nrm, self.floor)

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        return Dataset.from_array(_NormalizeRows(self.floor)((), x), n=ds.n)

    def rowwise(self):
        return _NormalizeRows(float(self.floor)), ()


@dataclasses.dataclass(frozen=True)
class _NormalizeRows:
    """NormalizeRows' rows-in, rows-out function."""

    floor: float
    groups_only = True

    def __call__(self, arrays, x):
        del arrays
        nrm = jnp.linalg.norm(x, axis=-1, keepdims=True)
        return x / jnp.maximum(nrm, self.floor)


def _signed_sqrt(arrays, x):
    """SignedHellingerMapper's rows-in, rows-out function."""
    del arrays
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


_signed_sqrt.groups_only = True


@dataclasses.dataclass(eq=False)
class SignedHellingerMapper(Transformer):
    """Signed square-root power normalization: sign(x) * sqrt(|x|)
    (reference: nodes/stats/SignedHellingerMapper.scala:12; the Batch- matrix
    variant is the same expression on a matrix)."""

    def apply(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))

    def apply_batch(self, ds: Dataset) -> Dataset:
        return Dataset.from_array(_signed_sqrt((), ds.padded()), n=ds.n)

    def rowwise(self):
        return _signed_sqrt, ()

    def eq_key(self):
        return ("signed_hellinger",)


@dataclasses.dataclass(eq=False)
class StandardScalerModel(Transformer):
    """x -> (x - mean) / std (std division optional). Padding rows are
    re-zeroed after centering so downstream Gram-matrix math stays exact
    (reference: nodes/stats/StandardScaler.scala:16)."""

    mean: Any  # (d,)
    std: Optional[Any] = None  # (d,) or None

    def apply(self, x):
        out = x - self.mean
        if self.std is not None:
            out = out / self.std
        return out

    def apply_batch(self, ds: Dataset) -> Dataset:
        out = _standardize(ds.padded(), self.mean, self.std, ds.mask())
        return Dataset.from_array(out, n=ds.n)


@jax.jit
def _standardize(x, mean, std, mask):
    """One pass: eager, ``x - mean``, ``/ std`` and ``* mask`` each hold a
    copy of ``x`` (4 GB of RandomPatchCifar's features on one chip)."""
    out = x - mean
    if std is not None:
        out = out / std
    return out * mask[:, None]


@jax.jit
def _moments(x):
    """Column sums of x and x², without a copy of x²."""
    return jnp.sum(x, axis=0), jnp.sum(x * x, axis=0)


@dataclasses.dataclass(eq=False)
class StandardScaler(Estimator):
    """Column mean/std via one sharded reduction pass (reference:
    nodes/stats/StandardScaler.scala:38 — treeAggregate of a
    MultivariateOnlineSummarizer; here the all-reduce is the XLA psum that
    jit inserts for the sum over the sharded example axis). Unbiased
    variance (n-1), eps guard matching MLlib behavior."""

    normalize_std_dev: bool = True
    eps: float = 1e-12

    def fit(self, data: Dataset) -> StandardScalerModel:
        x = data.padded()
        n = data.n
        s1, s2 = _moments(x)  # pad rows are zero — exact
        mean = s1 / n
        if not self.normalize_std_dev:
            return StandardScalerModel(mean, None)
        var = (s2 - n * mean * mean) / max(n - 1, 1)
        std = jnp.sqrt(jnp.maximum(var, 0.0))
        std = jnp.where(std < self.eps, 1.0, std)
        return StandardScalerModel(mean, std)


@dataclasses.dataclass(eq=False)
class CosineRandomFeatures(Transformer):
    """Random Fourier features cos(x Wᵀ + b) (reference:
    nodes/stats/CosineRandomFeatures.scala:19,49 — batch path is one GEMM
    with broadcast W; here one MXU matmul + fused cos)."""

    W: Any  # (num_features, d)
    b: Any  # (num_features,)

    @staticmethod
    def create(
        d: int,
        num_features: int,
        gamma: float,
        seed: int = 0,
        distribution: str = "gaussian",
    ) -> "CosineRandomFeatures":
        rng = np.random.default_rng(seed)
        if distribution == "cauchy":
            w = rng.standard_cauchy((num_features, d)) * gamma
        else:
            w = rng.standard_normal((num_features, d)) * gamma
        b = rng.uniform(0.0, 2.0 * np.pi, num_features)
        return CosineRandomFeatures(
            jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32)
        )

    def apply(self, x):
        return jnp.cos(mm(x, self.W.T) + self.b)

    def apply_batch(self, ds: Dataset) -> Dataset:
        x = ds.padded()
        with jax.named_scope("features.cosine"):
            out = jnp.cos(mm(x, self.W.T) + self.b)
            # cos(0 + b) != 0: keep the pad-rows-are-zero invariant
            out = out * ds.mask()[:, None]
        return Dataset.from_array(out, n=ds.n)


@dataclasses.dataclass(eq=False)
class TermFrequency(Transformer):
    """term sequence -> {term: weighted count} with a pluggable weighting
    function (reference: nodes/stats/TermFrequency.scala:19)."""

    fn: Callable[[float], float] = lambda x: x
    vmap_batch = False

    def apply(self, terms):
        # Counter consumes the generator at C speed — this node is on
        # the hot host path of every text pipeline (ngram lists become
        # hashable tuples on the way in)
        counts = Counter(
            tuple(t) if isinstance(t, list) else t for t in terms
        )
        return {k: self.fn(v) for k, v in counts.items()}

    def eq_key(self):
        return ("term_frequency", self.fn)


class ColumnSampler(Transformer):
    """Sample ``num_cols`` columns of each (d, m) matrix datum — used to
    subsample per-image descriptor sets before PCA/GMM fits (reference:
    nodes/stats/Sampling.scala:12).

    A batch of matrices is sampled on the device: the columns of every
    datum are drawn on the host at once (datum i of this sampler's life
    from ``default_rng((seed, i))``, the draw ``apply`` makes), put as one
    index array, and taken a gather a chunk; the matrices themselves never
    visit the host, and ragged ones (``Dataset`` shape groups) come
    through a chunk at a time, each dropped once its columns are taken.
    Span ``stats.column_sample``, scope ``stats.sample``, counter
    ``keystone_sampled_columns_total``."""

    vmap_batch = False

    def __init__(self, num_cols: int, seed: int = 0):
        self.num_cols = num_cols
        self.seed = seed
        self._counter = 0

    def draw(self, columns: int) -> np.ndarray:
        """The next datum's column indices, given how many it has."""
        # independent draw per datum (reference samples per image)
        rng = np.random.default_rng((self.seed, self._counter))
        self._counter += 1
        return rng.integers(0, columns, self.num_cols)

    def apply(self, m):
        arr = np.asarray(m)
        return jnp.asarray(arr[:, self.draw(arr.shape[1])])

    def apply_batch(self, ds: Dataset) -> Dataset:
        groups = ds.grouped()
        if groups is None:  # items that are no single arrays
            return ds.map(self.apply)
        with span("stats.column_sample", n=ds.n, cols=self.num_cols):
            columns = np.empty(ds.n, np.int64)
            for places, one in groups.group_rows():
                columns[places] = one.shape[2]
            idx = jnp.asarray(
                np.stack([self.draw(int(m)) for m in columns]), jnp.int32)
            parts, order = [], []
            for places, rows in groups.chunks():
                parts.append(_take_columns(rows, idx, jnp.asarray(places)))
                order.append(places)
            out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            order = np.concatenate(order)
            if not np.array_equal(order, np.arange(ds.n)):
                out = jnp.take(out, jnp.asarray(np.argsort(order)), axis=0)
        get_global_registry().counter(
            "keystone_sampled_columns_total",
            "columns ColumnSampler took from batches of matrices",
        ).inc(by=ds.n * self.num_cols)
        return Dataset.from_array(out, n=ds.n)

    def eq_key(self):
        return ("column_sampler", self.num_cols, self.seed)


@jax.jit
def _take_columns(rows, idx, places):
    """Columns ``idx[places[j]]`` of matrix j of ``rows`` (c, d, m)."""
    with jax.named_scope("stats.sample"):
        cols = jnp.take(idx, places, axis=0)  # (c, s)
        # a matrix at a time: one gather over the whole chunk would index
        # into gigabytes (58 images' descriptors are 2.2 GB)
        return jax.lax.map(
            lambda one: jnp.take(one[0], one[1], axis=1), (rows, cols))


class Sampler(FunctionNode):
    """Eager takeSample of ~``size`` examples (reference:
    nodes/stats/Sampling.scala:28)."""

    def __init__(self, size: int, seed: int = 0):
        self.size = size
        self.seed = seed

    def apply(self, data: Any) -> Dataset:
        ds = Dataset.of(data)
        rng = np.random.default_rng(self.seed)
        k = min(self.size, ds.n)
        idx = np.sort(rng.choice(ds.n, size=k, replace=False))
        if ds.is_array and not isinstance(ds.padded(), tuple):
            x = np.asarray(ds.array())
            return Dataset.from_array(jnp.asarray(x[idx]), n=k)
        items = ds.items()
        return Dataset.from_items([items[i] for i in idx])
