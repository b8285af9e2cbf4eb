"""VOCSIFTFisher end-to-end on a tar and labels file in the layout of the
reference's voctest.tar fixture."""

import numpy as np
import pytest

from keystone_tpu.loaders.image_loaders import VOCLoader
from keystone_tpu.pipelines.images.voc_sift_fisher import (
    SIFTFisherConfig,
    run,
)

from jpeg_fixtures import make_image_tar  # noqa: E402


@pytest.fixture
def voc(tmp_path):
    """The layout of the reference's ``voctest.tar`` / ``voclabels.csv``:
    a tar of JPEGs and a CSV of (id, class, classname, traintesteval,
    filename) rows, 1-based classes, one image under two classes and one
    in no row."""
    d = tmp_path / "voc"
    d.mkdir()
    make_image_tar(str(d / "voctest.tar"), "img", 7, size=(112, 104),
                   seed0=11)
    rows = ["id,class,classname,traintesteval,filename"]
    rows += [
        "1,1,aeroplane,train,VOC2007/img_0.JPEG",
        "2,2,bicycle,train,VOC2007/img_1.JPEG",
        "3,1,aeroplane,train,VOC2007/img_2.JPEG",
        "4,3,bird,train,VOC2007/img_2.JPEG",
        "5,2,bicycle,train,VOC2007/img_3.JPEG",
        "6,3,bird,train,VOC2007/img_4.JPEG",
        "7,1,aeroplane,train,VOC2007/img_5.JPEG",
    ]
    labels = tmp_path / "voclabels.csv"
    labels.write_text("\n".join(rows) + "\n")
    return str(d), str(labels)


def test_voc_loader_reads_reference_fixture(voc):
    ds = VOCLoader(*voc)
    items = ds.items()
    assert [li.filename for li in items] == [
        f"img_{i}.JPEG" for i in range(6)
    ]  # img_6 has no row and is dropped
    assert [li.labels for li in items] == [
        [0], [1], [0, 2], [1], [2], [0]
    ]
    first = ds.first()
    assert first.image.shape == (104, 112, 3)


def test_voc_sift_fisher_end_to_end(mesh8, voc):
    ds = VOCLoader(*voc)
    # shrink images for test speed
    small = ds.map(
        lambda li: type(li)(
            li.image[:96, :96], li.label, li.filename
        )
    )
    for a, b in zip(small.items(), ds.items()):
        a.labels = b.labels
    conf = SIFTFisherConfig(
        desc_dim=8, vocab_size=2, lam=0.5,
        num_pca_samples_per_image=20, num_gmm_samples_per_image=20,
    )
    predictor, mean_ap = run(small, small, conf)
    assert 0.0 <= mean_ap <= 1.0
