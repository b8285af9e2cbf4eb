"""Core image nodes: convolution, pooling, rectification, patch extraction.

Reference: nodes/images/{Convolver,Pooler,SymmetricRectifier,Windower,
CenterCornerPatcher,RandomPatcher,RandomImageTransformer,Cropper}.scala and
the small utilities in nodes/images/*.scala (ImageVectorizer, PixelScaler,
GrayScaler); image conventions from utils/images/Image.scala.

Conventions: an image is a jnp array ``A[x, y, c]`` (the reference's
``Image.get(x, y, channel)``); channel-major vectorization flattens as
``vec[c + x·C + y·C·X]`` (ChannelMajorArrayVectorizedImage), i.e.
``A.transpose(1, 0, 2).ravel()``.

TPU-first: the Convolver is NOT an im2col + GEMM translation. Patch
normalization and whitening are folded into closed-form corrections around
one XLA convolution (which the compiler maps onto the MXU):

    out = (conv(A, W) − m·S_f) / sd − ⟨μ_zca, W_f⟩

where m/sd are per-patch mean/std obtained from two box-filter convs.
This reproduces makePatches(normalizePatches)+whitener-mean-subtraction+
GEMM (Convolver.scala:128-205) without materializing a patch matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.utils.precision import mm
from keystone_tpu.workflow.api import FunctionNode, Transformer, run_rowwise

# MATLAB rgb2gray weights (reference: utils/images/ImageUtils.scala:73-76)
GRAYSCALE_WEIGHTS = (0.2989, 0.5870, 0.1140)


def channel_major_vectorize(img: jnp.ndarray) -> jnp.ndarray:
    """A[x,y,c] -> vec[c + x·C + y·C·X] (ChannelMajor flatten)."""
    return jnp.transpose(img, (1, 0, 2)).reshape(-1)


def pack_filters(filters: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stack filter images into the (num_filters, k·k·C) matrix layout of
    Convolver.packFilters (row i, col c + x·C + y·C·k = filter_i[x,y,c])."""
    return jnp.stack([channel_major_vectorize(f) for f in filters])


@dataclasses.dataclass(eq=False)
class Convolver(Transformer):
    """Convolve images with a filter bank (reference: Convolver.scala:20).

    ``filters``: (num_filters, k·k·C) packed rows (optionally already
    whitened, as RandomPatchCifar does); ``whitener``: the ZCAWhitener whose
    means are subtracted from each (normalized) patch.
    """

    filters: Any
    img_width: int
    img_height: int
    img_channels: int
    whitener: Optional[Any] = None
    normalize_patches: bool = True
    var_constant: float = 10.0
    fast: bool = False  # True trades ~0.4% feature error for MXU-native
    # speed: f32 inputs then run at TPU DEFAULT matmul precision (bf16
    # passes) instead of HIGHEST. The default keeps f32 semantics — the
    # patch-variance term s2 − P·m² cancels a decimal order on byte-range
    # images, which DEFAULT precision cannot represent.

    def __post_init__(self):
        C = self.img_channels
        k = int(np.sqrt(self.filters.shape[1] // C))
        self.conv_size = k
        F = self.filters.shape[0]
        # unpack rows (col c + x·C + y·C·k) back to W[f, x, y, c]
        self._W = jnp.transpose(
            jnp.asarray(self.filters, jnp.float32).reshape(F, k, k, C),
            (0, 2, 1, 3),
        )
        self._filter_sums = jnp.sum(self._W, axis=(1, 2, 3))  # S_f
        if self.whitener is not None:
            flat = self._W.transpose(0, 2, 1, 3).reshape(F, -1)
            self._whitener_dot = mm(flat, jnp.asarray(
                self.whitener.means, jnp.float32
            ))
        else:
            self._whitener_dot = None

    @property
    def res_width(self) -> int:
        return self.img_width - self.conv_size + 1

    @property
    def res_height(self) -> int:
        return self.img_height - self.conv_size + 1

    def apply(self, img):
        return self._convolve(img[None])[0]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(self._convolve(ds.padded()), n=ds.n)
        return ds.map(self.apply)

    def rowwise(self):
        return (
            _Convolve(
                self.conv_size, self.img_channels, self.normalize_patches,
                float(self.var_constant), self.fast,
            ),
            (self._W, self._filter_sums, self._whitener_dot),
        )

    def _convolve(self, imgs):
        """imgs: (n, X, Y, C) -> (n, resX, resY, F)."""
        fn, arrays = self.rowwise()
        return run_rowwise((fn,), (arrays,), imgs)


@dataclasses.dataclass(frozen=True)
class _Convolve:
    """The Convolver's rows-in, rows-out function, with its arrays as
    arguments: two Convolvers of equal settings share one compiled
    program whatever their filters (a fit builds its filters anew)."""

    conv_size: int
    channels: int
    normalize_patches: bool
    var_constant: float
    fast: bool

    def __call__(self, arrays, imgs):
        W, filter_sums, whitener_dot = arrays
        k, C = self.conv_size, self.channels
        x = imgs.astype(jnp.float32)
        hp = None if self.fast else jax.lax.Precision.HIGHEST
        # XLA correlation: out[n,x,y,f] = Σ A[n,x+dx,y+dy,c]·W[f,dx,dy,c]
        dn = jax.lax.conv_dimension_numbers(
            x.shape, W.shape, ("NHWC", "OHWI", "NHWC")
        )
        with jax.named_scope("conv.correlate"):
            raw = jax.lax.conv_general_dilated(
                x, W, (1, 1), "VALID", dimension_numbers=dn,
                preferred_element_type=jnp.float32, precision=hp,
            )
        if not self.normalize_patches and whitener_dot is None:
            return raw
        with jax.named_scope("conv.normalize"):
            P = k * k * C
            ones = jnp.ones((1, k, k, C), jnp.float32)
            s1 = jax.lax.conv_general_dilated(
                x, ones, (1, 1), "VALID", dimension_numbers=dn, precision=hp
            )
            out = raw
            if self.normalize_patches:
                s2 = jax.lax.conv_general_dilated(
                    x * x, ones, (1, 1), "VALID", dimension_numbers=dn,
                    precision=hp,
                )
                m = s1 / P
                # Stats.normalizeRows: var over patch entries, /(P-1),
                # +alpha
                var = (s2 - P * m * m) / (P - 1)
                sd = jnp.sqrt(var + self.var_constant)
                out = (raw - m * filter_sums[None, None, None, :]) / sd
            if whitener_dot is not None:
                out = out - whitener_dot[None, None, None, :]
            return out


@dataclasses.dataclass(eq=False)
class Pooler(Transformer):
    """Strided spatial pooling (reference: Pooler.scala:21 — strides start
    at poolSize/2, windows truncate at the image edge, pixel_fn applied
    before pooling, pool_fn reduces each window; sum by default)."""

    stride: int
    pool_size: int
    pixel_fn: Optional[Callable] = None
    pool_fn: Optional[Callable] = None

    def apply(self, img):
        return self._pool(img[None])[0]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(self._pool(ds.padded()), n=ds.n)
        return ds.map(self.apply)

    def rowwise(self):
        return (
            _Pool(self.stride, self.pool_size, self.pixel_fn, self.pool_fn),
            (),
        )

    def _pool(self, imgs):
        fn, arrays = self.rowwise()
        return run_rowwise((fn,), (arrays,), imgs)


@dataclasses.dataclass(frozen=True)
class _Pool:
    """The Pooler's rows-in, rows-out function (see ``_Convolve``)."""

    stride: int
    pool_size: int
    pixel_fn: Optional[Callable]
    pool_fn: Optional[Callable]

    def __call__(self, arrays, imgs):
        del arrays
        x_dim, y_dim = imgs.shape[1], imgs.shape[2]
        half = self.pool_size // 2
        start = half
        xs = list(range(start, x_dim, self.stride))
        ys = list(range(start, y_dim, self.stride))
        with jax.named_scope("conv.pool"):
            vals = imgs.astype(jnp.float32)
            if self.pixel_fn is not None:
                vals = self.pixel_fn(vals)
            pool_fn = self.pool_fn or (lambda w: jnp.sum(w, axis=(1, 2)))
            rows = []
            for px in xs:
                cols = []
                for py in ys:
                    window = vals[
                        :, px - half : min(px + half, x_dim),
                        py - half : min(py + half, y_dim), :,
                    ]
                    cols.append(pool_fn(window))
                rows.append(jnp.stack(cols, axis=1))  # (n, ny, C)
            return jnp.stack(rows, axis=1)  # (n, nx, ny, C)


@dataclasses.dataclass(eq=False)
class SymmetricRectifier(Transformer):
    """Two-sided ReLU doubling the channel count: channels [0,C) are
    max(maxVal, x−α), channels [C,2C) are max(maxVal, −x−α)
    (reference: SymmetricRectifier.scala:7)."""

    max_val: float = 0.0
    alpha: float = 0.0

    def apply(self, img):
        return self.rowwise()[0]((), img)

    def rowwise(self):
        return _Rectify(float(self.max_val), float(self.alpha)), ()

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            out = self.rowwise()[0]((), ds.padded())
            if self.max_val > 0 or self.alpha < 0:
                out = out * ds.mask().reshape(
                    (-1,) + (1,) * (out.ndim - 1)
                )
            return Dataset.from_array(out, n=ds.n)
        return ds.map(self.apply)


@dataclasses.dataclass(frozen=True)
class _Rectify:
    """The SymmetricRectifier's rows-in, rows-out function."""

    max_val: float
    alpha: float

    def __call__(self, arrays, x):
        del arrays
        with jax.named_scope("conv.rectify"):
            pos = jnp.maximum(self.max_val, x - self.alpha)
            neg = jnp.maximum(self.max_val, -x - self.alpha)
            return jnp.concatenate([pos, neg], axis=-1)


class ImageVectorizer(Transformer):
    """Image -> channel-major vector (reference:
    nodes/images/ImageVectorizer.scala)."""

    def apply(self, img):
        return channel_major_vectorize(img)

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(_vectorize((), ds.padded()), n=ds.n)
        return ds.map(self.apply)

    def rowwise(self):
        return _vectorize, ()

    def eq_key(self):
        return ("image_vectorizer",)


def _vectorize(arrays, x):
    """The ImageVectorizer's rows-in, rows-out function."""
    del arrays
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(x.shape[0], -1)


class PixelScaler(Transformer):
    """x / 255 (reference: nodes/images/PixelScaler.scala)."""

    def apply(self, img):
        return img.astype(jnp.float32) / 255.0

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(
                ds.padded().astype(jnp.float32) / 255.0, n=ds.n
            )
        return self._bucketed_batch(ds)

    def eq_key(self):
        return ("pixel_scaler",)


class GrayScaler(Transformer):
    """RGB -> single-channel grayscale with MATLAB rgb2gray weights
    (reference: GrayScaler.scala via ImageUtils.toGrayScale)."""

    def apply(self, img):
        w = jnp.asarray(GRAYSCALE_WEIGHTS, jnp.float32)
        return (img.astype(jnp.float32) @ w)[..., None]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            w = jnp.asarray(GRAYSCALE_WEIGHTS, jnp.float32)
            out = (ds.padded().astype(jnp.float32) @ w)[..., None]
            return Dataset.from_array(out, n=ds.n)
        return self._bucketed_batch(ds)

    def eq_key(self):
        return ("gray_scaler",)


@dataclasses.dataclass(eq=False)
class Cropper(Transformer):
    """Static crop [startX:endX, startY:endY] (reference:
    nodes/images/Cropper.scala)."""

    start_x: int
    start_y: int
    end_x: int
    end_y: int

    def apply(self, img):
        return img[self.start_x : self.end_x, self.start_y : self.end_y]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(
                ds.padded()[
                    :, self.start_x : self.end_x, self.start_y : self.end_y
                ],
                n=ds.n,
            )
        return ds.map(self.apply)


class Windower(FunctionNode):
    """Eagerly explode each image into all strided windows (reference:
    nodes/images/Windower.scala:13 — a FunctionNode flatMap)."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def apply(self, data) -> Dataset:
        ds = Dataset.of(data).to_array_mode()
        imgs = ds.padded()[: ds.n]
        k = self.window_size
        xs = range(0, imgs.shape[1] - k + 1, self.stride)
        ys = range(0, imgs.shape[2] - k + 1, self.stride)
        windows = [
            imgs[:, x : x + k, y : y + k, :] for x in xs for y in ys
        ]
        # (n·numWindows, k, k, C) — window-major within each image
        stacked = jnp.stack(windows, axis=1).reshape(
            (-1, k, k, imgs.shape[3])
        )
        return Dataset.from_array(stacked)


@dataclasses.dataclass(eq=False)
class RandomPatcher(Transformer):
    """Random crops for train augmentation (reference:
    RandomPatcher.scala:17): emits ``num_patches`` random (size x size)
    crops per image."""

    num_patches: int
    patch_size_x: int
    patch_size_y: int
    seed: int = 0
    vmap_batch = False

    def apply_batch(self, ds: Dataset) -> Dataset:
        ds = ds.to_array_mode()
        imgs = np.asarray(ds.padded()[: ds.n])
        rng = np.random.default_rng(self.seed)
        out = []
        px, py = self.patch_size_x, self.patch_size_y
        for img in imgs:
            for _ in range(self.num_patches):
                x = rng.integers(0, img.shape[0] - px + 1)
                y = rng.integers(0, img.shape[1] - py + 1)
                out.append(img[x : x + px, y : y + py])
        return Dataset.from_array(jnp.asarray(np.stack(out)))

    def apply(self, img):
        raise TypeError("RandomPatcher is a batch augmentation node")


@dataclasses.dataclass(eq=False)
class CenterCornerPatcher(Transformer):
    """Test-time augmentation: center + 4 corner crops, optionally with
    horizontal flips (reference: CenterCornerPatcher.scala:19)."""

    patch_size_x: int
    patch_size_y: int
    horizontal_flips: bool = False
    vmap_batch = False

    def _positions(self, X, Y):
        px, py = self.patch_size_x, self.patch_size_y
        return [
            (0, 0),
            (X - px, 0),
            (0, Y - py),
            (X - px, Y - py),
            ((X - px) // 2, (Y - py) // 2),
        ]

    def apply_batch(self, ds: Dataset) -> Dataset:
        ds = ds.to_array_mode()
        imgs = ds.padded()[: ds.n]
        X, Y = imgs.shape[1], imgs.shape[2]
        px, py = self.patch_size_x, self.patch_size_y
        crops = []
        for (x, y) in self._positions(X, Y):
            crop = imgs[:, x : x + px, y : y + py, :]
            crops.append(crop)
            if self.horizontal_flips:
                crops.append(crop[:, :, ::-1, :])
        # patch-major within each image: (n·numPatches, px, py, C)
        return Dataset.from_array(
            jnp.stack(crops, axis=1).reshape((-1, px, py, imgs.shape[3]))
        )

    def apply(self, img):
        raise TypeError("CenterCornerPatcher is a batch augmentation node")

    @property
    def patches_per_image(self) -> int:
        return 10 if self.horizontal_flips else 5


@dataclasses.dataclass(eq=False)
class RandomImageTransformer(Transformer):
    """Random horizontal flip with probability ``flip_chance``
    (reference: RandomImageTransformer.scala)."""

    flip_chance: float = 0.5
    seed: int = 0
    vmap_batch = False

    def apply_batch(self, ds: Dataset) -> Dataset:
        ds = ds.to_array_mode()
        imgs = ds.padded()
        rng = np.random.default_rng(self.seed)
        flips = jnp.asarray(
            rng.random(imgs.shape[0]) < self.flip_chance
        )
        flipped = imgs[:, :, ::-1, :]
        out = jnp.where(flips[:, None, None, None], flipped, imgs)
        return Dataset.from_array(out, n=ds.n)

    def apply(self, img):
        return img
