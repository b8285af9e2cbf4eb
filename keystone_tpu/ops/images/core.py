"""Core image nodes: convolution, pooling, rectification, patch extraction.

Reference: nodes/images/{Convolver,Pooler,SymmetricRectifier,Windower,
CenterCornerPatcher,RandomPatcher,RandomImageTransformer,Cropper}.scala and
the small utilities in nodes/images/*.scala (ImageVectorizer, PixelScaler,
GrayScaler); image conventions from utils/images/Image.scala.

Conventions: an image is a jnp array ``A[x, y, c]`` (the reference's
``Image.get(x, y, channel)``); channel-major vectorization flattens as
``vec[c + x·C + y·C·X]`` (ChannelMajorArrayVectorizedImage), i.e.
``A.transpose(1, 0, 2).ravel()``.

TPU-first: the Convolver is NOT an im2col + GEMM translation. Alone,
patch normalization and whitening are folded into closed-form
corrections around one XLA convolution (which the compiler maps onto
the MXU):

    out = (conv(A, W) − m·S_f) / sd − ⟨μ_zca, W_f⟩

where m/sd are per-patch mean/std obtained from two box-filter convs.
This reproduces makePatches(normalizePatches)+whitener-mean-subtraction+
GEMM (Convolver.scala:128-205) without materializing a patch matrix.

Where a Convolver feeds a SymmetricRectifier that feeds a sum Pooler
and nothing else reads between them (one ``RowwiseRun``), the three run
as one function, ``_ConvolveRectifyPool``: the maps, 27·27·F floats an
image where 2·2·2F leave the Pooler, are made, rectified and summed
into their windows a tile at a time in VMEM
(``pallas_kernels.conv_rectify_pool``) and never reach HBM. That
function does hold the patch matrix — (positions, k·k·C) an image, a
hundredth of the maps — so it normalizes the patches themselves,
``(p − m)/sd``, which is the same ``(conv(A, W) − m·S_f)/sd``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.ops.images.pallas_kernels import conv_rectify_pool
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

    def absorb(self, rest):
        """A rectifier and then a sum pooler behind this function make
        one function with it (``workflow.api.fold_rowwise``): no map
        leaves the chip. Any other successor, a pooler with a function
        of its own, or windows that overlap more than two deep along an
        axis (the kernel unrolls over the pieces the windows cut the map
        into) leave the three as they are."""
        if (
            len(rest) >= 2
            and isinstance(rest[0], _Rectify)
            and isinstance(rest[1], _Pool)
            and rest[1].pixel_fn is None
            and rest[1].pool_fn is None
            and rest[1].pool_size // 2 <= rest[1].stride
        ):
            return _ConvolveRectifyPool(self, rest[0], rest[1]), 2
        return None

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

    def windows(self, dim: int) -> List[tuple]:
        """The [start, stop) ranges the windows take along an axis of
        ``dim`` positions."""
        half = self.pool_size // 2
        return [
            (p - half, min(p + half, dim))
            for p in range(half, dim, self.stride)
        ]

    def __call__(self, arrays, imgs):
        del arrays
        with jax.named_scope("conv.pool"):
            vals = imgs.astype(jnp.float32)
            if self.pixel_fn is not None:
                vals = self.pixel_fn(vals)
            pool_fn = self.pool_fn or (lambda w: jnp.sum(w, axis=(1, 2)))
            rows = []
            for x0, x1 in self.windows(imgs.shape[1]):
                cols = [
                    pool_fn(vals[:, x0:x1, y0:y1, :])
                    for y0, y1 in self.windows(imgs.shape[2])
                ]
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


# images whose patches _ConvolveRectifyPool makes together: few enough
# for XLA to keep them in VMEM between their fusions and the kernel
PATCH_GROUP = 64


def _axis_cells(windows: Sequence[tuple]) -> List[tuple]:
    """An axis cut at every window's edge: ``(start, stop, windows that
    hold the piece)`` for each piece that some window holds."""
    edges = sorted({e for w in windows for e in w})
    cells = []
    for a, b in zip(edges, edges[1:]):
        inside = tuple(
            i for i, (lo, hi) in enumerate(windows) if lo <= a and b <= hi
        )
        if inside:
            cells.append((a, b, inside))
    return cells


@dataclasses.dataclass(frozen=True)
class _ConvolveRectifyPool:
    """``_Convolve`` → ``_Rectify`` → a sum ``_Pool`` as one rows-in,
    rows-out function (``_Convolve.absorb``), the same numbers with
    another order of summation. The pooling windows cut the map into
    rectangles, each inside the same windows throughout. An image's
    patches are laid out rectangle after rectangle, normalized as the
    Convolver's closed form does — ``(p − m)/sd`` is
    ``(conv − m·S_f)/sd`` — and ``conv_rectify_pool`` makes, rectifies
    and sums the responses a (positions, filter tile) slab at a time in
    VMEM; a window is the sum of its rectangles. Its arrays are the
    three functions' arrays, one after another."""

    conv: _Convolve
    rect: _Rectify
    pool: _Pool

    def _layout(self, imgs):
        """For the map's two axes: how many windows, and the pieces
        their edges cut the axis into (``_axis_cells``)."""
        k = self.conv.conv_size
        windows = [self.pool.windows(dim - k + 1) for dim in imgs.shape[1:3]]
        return [len(w) for w in windows], [_axis_cells(w) for w in windows]

    def _patches(self, imgs):
        """(n, R, K): the patches of every rectangle, rows in the order
        of the rectangles, columns ``(dx, dy, c)`` as ``W[f]`` flattens;
        R is padded to the sublane tile and K to the lane tile with
        zeros."""
        k = self.conv.conv_size
        x = imgs.astype(jnp.float32)
        n, x_dim, y_dim, channels = x.shape
        rx, ry = x_dim - k + 1, y_dim - k + 1
        every = jnp.stack(
            [x[:, dx:dx + rx, dy:dy + ry, :]
             for dx in range(k) for dy in range(k)],
            axis=3,
        ).reshape(n, rx, ry, k * k * channels)
        _, (x_cells, y_cells) = self._layout(imgs)
        p = jnp.concatenate(
            [
                every[:, x0:x1, y0:y1, :].reshape(
                    n, (x1 - x0) * (y1 - y0), every.shape[3]
                )
                for x0, x1, _ in x_cells for y0, y1, _ in y_cells
            ],
            axis=1,
        )
        if self.conv.normalize_patches:
            # Stats.normalizeRows: var over patch entries, /(P-1), +alpha
            centred = p - jnp.mean(p, axis=2, keepdims=True)
            var = jnp.sum(centred * centred, axis=2, keepdims=True) / (
                p.shape[2] - 1
            )
            p = centred / jnp.sqrt(var + self.conv.var_constant)
        return jnp.pad(
            p, ((0, 0), (0, -p.shape[1] % 8), (0, -p.shape[2] % 128))
        )

    def _sums(self, arrays, imgs):
        """(patches, the kernel's sums (n, 2·windows, F)) of a group of
        images."""
        (W, _, whitener_dot), _, _ = arrays
        with jax.named_scope("conv.patches"):
            patches = self._patches(imgs)
        (nx, ny), (x_cells, y_cells) = self._layout(imgs)
        # the rectangles' rows in ``patches``, and each window's rectangles
        sizes = [
            (x1 - x0) * (y1 - y0)
            for x0, x1, _ in x_cells for y0, y1, _ in y_cells
        ]
        stops = np.cumsum(sizes)
        windows = [
            [
                i * len(y_cells) + j
                for i, (_, _, in_x) in enumerate(x_cells) if wx in in_x
                for j, (_, _, in_y) in enumerate(y_cells) if wy in in_y
            ]
            for wx in range(nx) for wy in range(ny)
        ]
        num_filters = W.shape[0]
        w = jnp.pad(
            W.reshape(num_filters, -1).T,
            ((0, patches.shape[2] - W[0].size), (0, 0)),
        )
        bias = (
            jnp.zeros((num_filters,), jnp.float32)
            if whitener_dot is None else whitener_dot
        )
        with jax.named_scope("conv.rectify_pool"):
            sums = conv_rectify_pool(
                patches, w, bias[None, :],
                segments=[
                    (int(stop - size), int(stop))
                    for size, stop in zip(sizes, stops)
                ],
                windows=windows,
                max_val=self.rect.max_val, alpha=self.rect.alpha,
                precision=(
                    None if self.conv.fast else jax.lax.Precision.HIGHEST
                ),
            )
        return patches, sums

    @staticmethod
    def _groups(n: int) -> tuple:
        """(groups, images a group): ``n`` images divided evenly over
        the groups that ``PATCH_GROUP`` images a group ask for."""
        groups = -(-n // PATCH_GROUP)
        return groups, -(-n // groups)

    def held(self, arrays, imgs):
        """What the rows keep on the device beside their result: the
        patches of one group of images, and the kernel's sums (n,
        2·windows, F) before they are put in the Pooler's order
        (``workflow.api.plan_rowwise_run`` counts both)."""
        groups, size = self._groups(imgs.shape[0])
        if groups == 1:
            return self._sums(arrays, imgs)
        grouped = jnp.pad(
            imgs, ((0, groups * size - imgs.shape[0]),) + ((0, 0),) * 3
        ).reshape((groups, size) + imgs.shape[1:])
        # a group's sums leave the loop as (size, 2·windows · F)
        sums = jax.lax.map(
            lambda g: self._sums(arrays, g)[1].reshape(size, -1), grouped
        )
        (nx, ny), _ = self._layout(imgs)
        return (
            jax.eval_shape(self._patches, grouped[0]),
            sums.reshape(groups * size, 2 * nx * ny, -1)[:imgs.shape[0]],
        )

    def __call__(self, arrays, imgs):
        _, sums = self.held(arrays, imgs)
        n, _, num_filters = sums.shape
        (nx, ny), _ = self._layout(imgs)
        # (n, [pos | neg], nx·ny, F) -> (n, nx, ny, [pos F | neg F])
        return jnp.transpose(
            sums.reshape(n, 2, nx, ny, num_filters), (0, 2, 3, 1, 4)
        ).reshape(n, nx, ny, 2 * num_filters)


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
            return Dataset.from_array(_scale_pixels((), ds.padded()), n=ds.n)
        return self._bucketed_batch(ds)

    def rowwise(self):
        return _scale_pixels, ()

    def eq_key(self):
        return ("pixel_scaler",)


def _scale_pixels(arrays, x):
    """PixelScaler's rows-in, rows-out function."""
    del arrays
    return x.astype(jnp.float32) / 255.0


_scale_pixels.groups_only = True


class GrayScaler(Transformer):
    """RGB -> single-channel grayscale with MATLAB rgb2gray weights
    (reference: GrayScaler.scala via ImageUtils.toGrayScale)."""

    def apply(self, img):
        w = jnp.asarray(GRAYSCALE_WEIGHTS, jnp.float32)
        return (img.astype(jnp.float32) @ w)[..., None]

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array:
            return Dataset.from_array(_to_gray((), ds.padded()), n=ds.n)
        return self._bucketed_batch(ds)

    def rowwise(self):
        return _to_gray, ()

    def eq_key(self):
        return ("gray_scaler",)


def _to_gray(arrays, x):
    """GrayScaler's rows-in, rows-out function."""
    del arrays
    w = jnp.asarray(GRAYSCALE_WEIGHTS, jnp.float32)
    return (x.astype(jnp.float32) @ w)[..., None]


_to_gray.groups_only = True


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


@partial(jax.jit, static_argnames=("num", "px", "py"))
def _random_crops(imgs, x0, y0, *, num: int, px: int, py: int):
    """Crop j = imgs[j // num, x0_j : x0_j + px, y0_j : y0_j + py],
    gathered on the device where the images are: one gather of whole
    windows, a pixel's channels beside its y (one minor axis of Y·C
    values), a loop of a step a crop on the chip:
    ``random_patch_cifar._gather_windows`` takes its patches by a form
    that has none, into another layout than crops want (PERF.md, PR 34)."""
    n, x_dim, y_dim = imgs.shape[:3]
    channels = int(np.prod(imgs.shape[3:], dtype=np.int64))
    rows = imgs.reshape(n, x_dim, y_dim * channels)
    crops = jax.vmap(
        lambda i, x, y: jax.lax.dynamic_slice(
            rows, (i, x, y * channels), (1, px, py * channels)
        )[0]
    )(jnp.arange(x0.shape[0]) // num, x0, y0)
    return crops.reshape((-1, px, py) + imgs.shape[3:])


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

    def offsets(self, n: int, x_dim: int, y_dim: int) -> np.ndarray:
        """(n · num_patches, 2): each crop's (x, y) origin, image by
        image. One draw; the values are those of one ``rng.integers``
        call for x and one for y, crop after crop."""
        rng = np.random.default_rng(self.seed)
        high = np.array(
            [x_dim - self.patch_size_x + 1, y_dim - self.patch_size_y + 1]
        )
        return rng.integers(0, high, size=(n * self.num_patches, 2))

    def apply_batch(self, ds: Dataset) -> Dataset:
        ds = ds.to_array_mode()
        imgs = ds.array()
        off = self.offsets(imgs.shape[0], imgs.shape[1], imgs.shape[2])
        return Dataset.from_array(
            _random_crops(
                imgs, jnp.asarray(off[:, 0], jnp.int32),
                jnp.asarray(off[:, 1], jnp.int32),
                num=self.num_patches, px=self.patch_size_x,
                py=self.patch_size_y,
            )
        )

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
