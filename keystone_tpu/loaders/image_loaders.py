"""ImageNet / VOC tar-archive image loaders.

Reference: loaders/ImageNetLoader.scala:11 (tar archives -> labeled images
via a WNID->class map file), loaders/ImageLoaderUtils.scala:22-47
(per-file tar streaming + decode), loaders/VOCLoader.scala:15 (VOC2007
multi-label tar loader + voclabels.csv).

These are the EAGER loaders (materialize a ``Dataset`` of decoded
images) for datasets that fit in host RAM — tests, CIFAR-scale work,
fixture tars. They are thin collectors over the out-of-core streaming
substrate in ``loaders/streaming.py``; at ImageNet scale use
``StreamingImageNetLoader`` directly and never materialize.

Images decode to (x=row, y=col, c) float arrays.
"""

from __future__ import annotations

import dataclasses
import logging

import numpy as np

from keystone_tpu.loaders.streaming import (
    StreamingImageLoader,
    imagenet_label_fn,
    tar_shard_paths,
    voc_label_fn,
)
from keystone_tpu.parallel.dataset import Dataset

logger = logging.getLogger(__name__)

NUM_IMAGENET_CLASSES = 1000


@dataclasses.dataclass
class LabeledImage:
    image: np.ndarray
    label: int
    filename: str = ""


def ImageNetLoader(location: str, labels_path: str) -> Dataset:
    """Load labeled ImageNet images from tar archive(s). ``labels_path``
    maps WNID -> integer class ("n15075141 12" lines, reference:
    ImageNetLoader.scala label map)."""
    stream = StreamingImageLoader(
        tar_shard_paths(location, 0, 1), imagenet_label_fn(labels_path)
    )
    items = [
        LabeledImage(arr, label, name) for name, label, arr in stream.items()
    ]
    logger.info(
        "ImageNetLoader %s: %d images, decode path %s",
        location, len(items), dict(stream.decode_counts),
    )
    return Dataset.from_items(items)


def VOCLoader(location: str, labels_path: str) -> Dataset:
    """VOC2007 loader: labels CSV has (id, class, classname, traintesteval,
    filename) rows; an image may appear under several classes (multi-label,
    reference: VOCLoader.scala:15)."""
    stream = StreamingImageLoader(
        tar_shard_paths(location, 0, 1), voc_label_fn(labels_path)
    )
    items = []
    for name, labels, arr in stream.items():
        li = LabeledImage(arr, -1, name.split("/")[-1])
        li.labels = labels  # multi-label
        items.append(li)
    return Dataset.from_items(items)


class ImageExtractor:
    """LabeledImage dataset -> image dataset (reference:
    utils/LabeledImageExtractors)."""

    @staticmethod
    def apply(ds: Dataset) -> Dataset:
        return ds.map(lambda li: li.image)

    def __call__(self, ds: Dataset) -> Dataset:
        return self.apply(ds)


class LabelExtractor:
    @staticmethod
    def apply(ds: Dataset) -> Dataset:
        import jax.numpy as jnp

        return Dataset.from_array(
            jnp.asarray([li.label for li in ds.items()], jnp.int32)
        )

    def __call__(self, ds: Dataset) -> Dataset:
        return self.apply(ds)


class MultiLabelExtractor:
    @staticmethod
    def apply(ds: Dataset) -> Dataset:
        return ds.map(lambda li: np.asarray(getattr(li, "labels", [li.label])))

    def __call__(self, ds: Dataset) -> Dataset:
        return self.apply(ds)
