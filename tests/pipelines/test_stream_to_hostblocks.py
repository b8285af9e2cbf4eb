"""The full out-of-core training flow, end to end: tar stream →
featurize per batch → features accumulated as HOST-RAM column blocks →
out-of-aggregate-HBM weighted BCD fit.

This is the reference's flagship workflow shape
(ImageNetSiftLcsFV.scala:106-142: stream-decode on executors, featurize,
cache features in cluster RAM, block-solve) composed from this
framework's pieces: StreamingImageNetLoader (bounded-memory decode),
``Dataset.host_blocks_from_batches`` (the cluster-RAM cache tier), and
``BlockWeightedLeastSquaresEstimator`` on host blocks (slab-streamed
PCG). Small CPU shapes; the contracts are composition correctness and
parity with the all-in-device-memory path.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import io
import tarfile

from jpeg_fixtures import jpeg_array
from tests.ops.test_weighted_ls import (
    _sorted_fits_total,
    ref_block_weighted_bcd,
)
from keystone_tpu.loaders.streaming import StreamingImageNetLoader
from keystone_tpu.ops.learning import BlockWeightedLeastSquaresEstimator
from keystone_tpu.ops.util.nodes import ClassLabelIndicators
from keystone_tpu.parallel.dataset import Dataset


def _class_tar(tar_path, wnid, cls, n):
    """A tar of images sharing a CLASS-coherent channel signature
    (class c is dominant in channel c) over per-image texture — so a
    linear model on pooled features can actually learn the classes."""
    from PIL import Image as PILImage

    gains = np.eye(3, dtype=np.float32) * 0.8 + 0.2
    with tarfile.open(tar_path, "w") as tf:
        for i in range(n):
            arr = jpeg_array(40, 40, cls * 977 + i).astype(np.float32)
            arr = np.clip(arr * gains[cls][None, None, :], 0, 255)
            buf = io.BytesIO()
            PILImage.fromarray(arr.astype(np.uint8)).save(
                buf, format="JPEG", quality=92
            )
            info = tarfile.TarInfo(f"{wnid}_{i}.JPEG")
            data = buf.getvalue()
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))


@pytest.fixture
def tar_dir(tmp_path):
    d = tmp_path / "tars"
    d.mkdir()
    wnids = ["n02000001", "n02000002", "n02000003"]
    for i, wnid in enumerate(wnids):
        _class_tar(str(d / f"{wnid}.tar"), wnid, i, 8)
    labels = tmp_path / "labels.txt"
    labels.write_text(
        "".join(f"{w} {i}\n" for i, w in enumerate(wnids))
    )
    return str(d), str(labels)


def _featurize(u8_batch):
    """A small whole-batch featurize standing in for the FV chain:
    downsample + flatten + a fixed random projection (device compute,
    fixed output width)."""
    x = jnp.asarray(u8_batch, jnp.float32) / 255.0
    pooled = x.reshape(x.shape[0], 8, 4, 8, 4, 3).mean(axis=(2, 4))
    flat = pooled.reshape(x.shape[0], -1)
    rng = np.random.default_rng(0)
    proj = jnp.asarray(
        rng.standard_normal((flat.shape[1], 96)).astype(np.float32) / 10
    )
    return flat @ proj


def test_stream_featurize_hostblocks_fit_end_to_end(tar_dir):
    loc, labels_path = tar_dir
    loader = StreamingImageNetLoader(
        loc, labels_path, decode_size=32, shard_index=0, num_shards=1,
    )

    ys = []

    def batches():
        for imgs, labs, nv in loader.batches(8, np.uint8):
            ys.extend(labs[:nv])
            yield _featurize(imgs[:nv])

    host_ds = Dataset.host_blocks_from_batches(batches(), block_size=32)
    assert host_ds.is_host
    assert host_ds.n == 24
    assert host_ds.block_widths == [32, 32, 32]

    y = np.asarray(ys, np.int32)
    labels = ClassLabelIndicators(3).apply_batch(
        Dataset.from_array(jnp.asarray(y))
    )
    est = BlockWeightedLeastSquaresEstimator(
        block_size=32, num_iter=2, lam=1e-3, mixture_weight=0.5,
        solve="pcg",
    )
    model = est.fit(host_ds, labels)

    # parity: the same features fit through the all-in-device path, at
    # its default row layout (the CG matvec on class-sorted rows, where
    # the slabs take one-hot products on the original rows)
    dense = np.concatenate(host_ds.host_blocks, axis=1)
    sorted_fits = _sorted_fits_total()
    dev = est.fit(
        Dataset.from_array(jnp.asarray(dense)), labels
    )
    assert _sorted_fits_total() == sorted_fits + 1
    # Both are held to the float64 direct solution of the same systems.
    # A block has 32 unknowns and 24 rows, so lam alone holds its
    # systems up (condition ~1.5e3), and a CG that stops at a relative
    # residual of pcg_tol is that far, times the condition, from it.
    conds = []
    W64, _ = ref_block_weighted_bcd(
        dense, np.asarray(labels.array()), 32, 2, 1e-3, 0.5, conds)
    bound = max(conds) * est.pcg_tol * np.abs(W64).max()
    err_slabs = np.abs(np.asarray(model.W) - W64).max()
    err_dev = np.abs(np.asarray(dev.W) - W64).max()
    assert max(err_slabs, err_dev) <= bound, (err_slabs, err_dev, bound)
    # The two layouts add the same float32 products up in different
    # orders (on these rows either layout's products lie 1e-7 from
    # their float64 values), and these systems amplify that: the class
    # covariance is a difference of terms 34 times its size, the
    # condition does the rest. Measured here, the slabs lie 6.6e-5 from
    # float64 and the sorted rows 7.6e-5. So the sorted rows may not
    # lie further than twice the slabs' distance, nor the two fits
    # further apart than those two distances allow. (On well-posed
    # systems slabs and sorted rows meet atol 2e-5:
    # tests/parallel/test_host_blocks.py.)
    assert err_dev <= 2 * err_slabs, (err_dev, err_slabs)
    np.testing.assert_allclose(
        np.asarray(model.W), np.asarray(dev.W), rtol=2e-4, atol=1.5e-4
    )

    # and the composed flow actually learned the classes
    preds = np.asarray(model.apply_batch(host_ds).array())
    assert (preds.argmax(1) == y).mean() == 1.0


def test_host_blocks_from_batches_contracts():
    with pytest.raises(ValueError, match="empty"):
        Dataset.host_blocks_from_batches(iter([]), block_size=8)
    ragged = iter([np.zeros((4, 16), np.float32),
                   np.zeros((4, 24), np.float32)])
    with pytest.raises(ValueError, match="width changed"):
        Dataset.host_blocks_from_batches(ragged, block_size=8)
    # uneven tail column block
    ds = Dataset.host_blocks_from_batches(
        iter([np.ones((2, 20), np.float32)] * 3), block_size=8
    )
    assert ds.block_widths == [8, 8, 4]
    assert ds.n == 6
