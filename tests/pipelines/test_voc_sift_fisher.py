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
        num_pca_samples=120, num_gmm_samples=120,
    )
    predictor, mean_ap = run(small, small, conf)
    assert 0.0 <= mean_ap <= 1.0


def test_per_image_draw_is_the_scala_files_division():
    from keystone_tpu.pipelines.images.voc_sift_fisher import (
        samples_per_image,
    )

    conf = SIFTFisherConfig()
    assert conf.num_pca_samples == conf.num_gmm_samples == 1_000_000
    assert samples_per_image(conf.num_pca_samples, 5011) == 199
    assert samples_per_image(conf.num_gmm_samples, 313) == 3194
    assert samples_per_image(10, 50) == 1


def _images(shapes, n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, shapes[i % len(shapes)] + (3,), dtype=np.uint8)
        for i in range(n)
    ]


def test_ragged_batch_stays_arrays_and_equals_the_uniform_batches():
    """Images of three shapes through PixelScaler -> GrayScaler -> SIFT ->
    PCA as one ragged Dataset: one array a shape all the way (no item is
    ever cut: ``array_items`` stays whole), and every image's reduced
    descriptors equal those the same nodes give its shape's images as one
    uniform batch."""
    import jax.numpy as jnp

    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
    from keystone_tpu.ops.images.sift import SIFTExtractor
    from keystone_tpu.ops.learning import BatchPCATransformer
    from keystone_tpu.parallel.dataset import Dataset

    shapes = [(48, 64), (64, 48), (56, 56)]
    images = _images(shapes, 8)
    basis = jnp.asarray(
        np.random.default_rng(1).standard_normal((128, 5)), jnp.float32)
    chain = (
        PixelScaler().and_then(GrayScaler())
        .and_then(SIFTExtractor(scale_step=0))
        .and_then(BatchPCATransformer(basis))
    )

    def count(name):
        return sum(
            s.value for f in get_global_registry().collect()
            if f.name == name for s in f.samples if s.suffix == "")

    reset_global_registry()
    try:
        out = chain(Dataset.from_items(images)).get()
        assert out.is_grouped
        groups = out.groups()
        assert len(groups) == 3
        assert count("keystone_workflow_items_total") \
            == count("keystone_workflow_array_items_total") > 0
        assert count("keystone_workflow_item_slices_total") == 0
        assert count("keystone_workflow_shape_groups_total") >= 3
        # a put a shape group, not an image
        assert count("keystone_workflow_h2d_transfers_total") == 3
    finally:
        reset_global_registry()
    for places, got in groups:
        same = [images[int(i)] for i in places]
        want = chain(Dataset.from_items(same)).get()
        assert want.is_array
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want.array()), rtol=1e-5, atol=1e-4)


def test_a_few_held_out_images_run_the_programs_the_training_images_compiled():
    """A second ragged batch with fewer images of each shape (a held-out
    set after a fit) is filled up to each shape's chunk: dense SIFT
    compiles nothing new, and every image's descriptors are those of the
    image alone."""
    from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
    from keystone_tpu.ops.images.sift import SIFTExtractor, _SiftRows
    from keystone_tpu.parallel import chunks
    from keystone_tpu.parallel.dataset import Dataset

    shapes = [(48, 64), (64, 48), (56, 56)]
    chain = (
        PixelScaler().and_then(GrayScaler())
        .and_then(SIFTExtractor(scale_step=0))
    )
    chain(Dataset.from_items(_images(shapes, 8, seed=3))).get().groups()
    program = chunks.rows_program(_SiftRows(3, 4, 4, 0))
    compiled = program._cache_size()
    held_out = _images(shapes, 4, seed=4)  # groups of 2, 1 and 1
    groups = chain(Dataset.from_items(held_out)).get().groups()
    assert program._cache_size() == compiled
    assert sorted(len(p) for p, _ in groups) == [1, 1, 2]
    for places, got in groups:
        for row, i in zip(np.asarray(got), places):
            alone = chain(Dataset.from_items([held_out[int(i)]])).get()
            np.testing.assert_allclose(
                row, np.asarray(alone.array())[0], rtol=1e-5, atol=1e-4)


def test_build_pipeline_on_three_shapes_matches_the_plain_reference():
    """``build_pipeline(...).fit()`` on host images of three shapes — the
    PCA, the GMM and the block model fitted from the images — against
    the benchmark's plain reference at every stage (PCA and samples drawn
    again from the seed, the k-means++ seeds against the float64 D² draw,
    the EM's model and stopping round, held-out Fisher vectors and
    scores), through the benchmark's own adapter at the tiny cell's size."""
    from benchmark import run
    from benchmark.programs import voc
    from benchmark.reference import voc_sift_fisher as ref
    from keystone_tpu.workflow.executor import PipelineEnv

    config = run.load_json(run.HERE, "tests", "tiny", "voc-sift-fisher.json")
    workload = run.load_json(run.HERE, "tests", "tiny", "voc-fit.json")
    ctx = run.Context({"name": "voc-fit", "chips": 1}, config, workload,
                      2 ** 31 + 5, 0.1, False)
    run.check_devices(ctx, require_chip=False)
    inputs = voc.make_inputs(ctx)
    assert len({x.shape for x in inputs["items"]}) == 3
    try:
        fitted = voc.fit(inputs)
        got = voc.outputs(fitted, inputs)
        sample = dict(voc.reference_inputs(inputs), outputs={"first": got})
        numbers = ref.stage_numbers(config, sample, got, {})
    finally:
        PipelineEnv.get_or_create().reset()
    assert got["scores"].shape == (6, 20) and got["pca"].shape == (128, 8)
    assert got["reason"] in ("tolerance", "max_iter", "cluster_floor")
    for name, limit in workload["limits"].items():
        assert numbers[name] <= limit, numbers
