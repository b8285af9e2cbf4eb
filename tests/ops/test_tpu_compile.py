"""Kernels of the benchmark's paths compiled for a TPU v5e that is
described and not attached, at the published widths: what Mosaic refuses
(a slice off the tiling, more VMEM than a kernel may use, a block it
cannot partition) fails here and costs no chip time. Nothing runs, so
nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library (keep such tests in
this one file)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# RandomPatchCifar at 10,000 filters: 79 filter tiles of 128, the last of
# 16; a chunk's image tiles whole, and a last image tile of one
@pytest.mark.parametrize("rows", [64, 3])
def test_conv_rectify_pool_compiles_at_the_published_widths(one_chip, rows):
    from keystone_tpu.ops.images.pallas_kernels import conv_rectify_pool

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    sizes = [169, 13, 169, 13, 1, 13, 169, 13, 169]
    stops = [sum(sizes[:i + 1]) for i in range(9)]
    segments = [(b - a, b) for a, b in zip(sizes, stops)]
    windows = [[0, 1, 3, 4], [1, 2, 4, 5], [3, 4, 6, 7], [4, 5, 7, 8]]

    def kernel(patches, w, bias):
        return conv_rectify_pool(
            patches, w, bias, segments=segments, windows=windows,
            max_val=0.0, alpha=0.25, interpret=False)

    compiled = jax.jit(kernel).lower(
        shape(rows, 736, 128), shape(128, 10000), shape(1, 10000)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert (compiled.memory_analysis().output_size_in_bytes
            >= rows * 8 * 10000 * 4)


def test_folded_chunk_program_writes_no_map_at_the_published_widths(one_chip):
    """A RowwiseRun's chunk program for RandomPatchCifar, folded, 3,136
    of 12,544 rows: it holds the kernel, and no fusion of it writes a
    four-dimensional float32 array whose last dimension is the filters
    — what the three functions' maps were, and what the benchmark's
    ``conv_roofline_pct.cfit`` takes for the convolution (a loop's
    stacked sums of that shape read 829% there: my chip run, PR 32)."""
    import json
    import os
    import re

    from keystone_tpu.ops.images import core, pallas_kernels
    from keystone_tpu.workflow import api

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fns, arrays = api.fold_rowwise(
        (core._Convolve(6, 3, True, 10.0, False), core._Rectify(0.0, 0.25),
         core._Pool(13, 14, None, None), core._vectorize),
        ((shape((10000, 6, 6, 3)), shape((10000,)), shape((10000,))),
         (), (), ()),
    )
    compile_kernel = pallas_kernels.auto_interpret
    pallas_kernels.auto_interpret = lambda interpret=None: False
    try:
        text = api._run_chunk.lower(
            fns, 3136, arrays, shape((12544, 80000)),
            shape((12544, 32, 32, 3)), shape((), jnp.int32),
            shape((), jnp.int32),
        ).compile().as_text()
    finally:
        pallas_kernels.auto_interpret = compile_kernel
    assert "tpu_custom_call" in text
    metric = os.path.join(
        os.path.dirname(__file__), "..", "..", "benchmark", "metrics",
        "conv_roofline_pct.cfit.json")
    with open(metric) as f:
        pattern = json.load(f)["args"]["pattern"].format(num_filters=10000)
    taken = [line.strip()[:120] for line in text.splitlines()
             if re.match(pattern, line.strip())]
    assert not taken, taken
    assert not re.search(r"f32\[\d+,27,27,[12]0000\]", text)


def test_folded_chunk_program_compiles_at_the_augmented_crops(one_chip):
    """The same run at its second geometry (cifar-krr-fit): 24 x 24
    crops, one filter tile of 512, 2 x 2 sum windows of 10 at stride 9
    that overlap by one position, so that the map is cut into nine
    rectangles; a chunk of 15,625 of 125,000 rows."""
    from keystone_tpu.ops.images import core, pallas_kernels
    from keystone_tpu.workflow import api

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fns, arrays = api.fold_rowwise(
        (core._Convolve(6, 3, True, 10.0, False), core._Rectify(0.0, 0.25),
         core._Pool(9, 10, None, None), core._vectorize),
        ((shape((512, 6, 6, 3)), shape((512,)), shape((512,))),
         (), (), ()),
    )
    assert len(fns) == 2  # folded: the three functions as one
    compile_kernel = pallas_kernels.auto_interpret
    pallas_kernels.auto_interpret = lambda interpret=None: False
    try:
        compiled = api._run_chunk.lower(
            fns, 15625, arrays, shape((125000, 4096)),
            shape((125000, 24, 24, 3)), shape((), jnp.int32),
            shape((), jnp.int32),
        ).compile()
    finally:
        pallas_kernels.auto_interpret = compile_kernel
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"f32\[\d+,19,19,(512|1024)\]", text)  # no map


def test_krr_block_program_and_the_roofline_metric_s_pattern(one_chip):
    """One Gauss-Seidel block step of cifar-krr-fit at the published
    widths (125,000 rows of 4,096 features, a block of 5,000, 10
    classes): it fits the chip beside three kept models, and the
    benchmark's ``krr_kernel_roofline_pct.kfit`` takes exactly one of
    its operations for the column block — the fusion that holds the
    cross term and writes K(:, B) — and nothing that reads it (a
    mistaken pattern read 829% in PR 32). The solve metric's pattern
    takes none of the row-sized operations."""
    import json
    import os

    from keystone_tpu.ops.learning import kernel

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    n, d, b, k = 125000, 4096, 5000, 10
    compiled = kernel._krr_block_step.lower(
        shape((n, d)), shape((n,)), 2e-4, shape((n,)), shape((n, k)),
        shape((n, k)), shape((), jnp.int32), 0.1, width=b,
    ).compile()
    memory = compiled.memory_analysis()
    # the rows in, one (n, b) column block as the only large temporary:
    # the exponential is fused into the cross term's output
    assert memory.argument_size_in_bytes < 2.2e9
    assert 4 * n * b <= memory.temp_size_in_bytes < 1.2 * 4 * n * b
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    ops = [line.strip() for line in entry.splitlines() if " = " in line]
    metrics = os.path.join(os.path.dirname(__file__), "..", "..",
                           "benchmark", "metrics")
    names = {"rows": n, "block_size": b, "num_classes": k}

    def pattern(metric):
        with open(os.path.join(metrics, metric + ".json")) as f:
            return re.compile(json.load(f)["args"]["pattern"].format(**names))

    taken = [op for op in ops
             if pattern("krr_kernel_roofline_pct.kfit").search(op)]
    assert len(taken) == 1, [op[:160] for op in taken]
    assert "krr.kernel_block/dot_general" in taken[0]
    assert pattern("krr_kernel_device_ms_per_fit.kfit").pattern == \
        pattern("krr_kernel_roofline_pct.kfit").pattern
    solve = pattern("krr_solve_device_ms_per_fit.kfit")
    rowsized = [op for op in ops if "[125000," in op]
    assert rowsized and not any(solve.search(op) for op in rowsized)
    assert any(solve.search(op) and "krr.solve" in op for op in ops)


def _while_bounds(text):
    """{while: the integer constants of its condition}: a counted
    loop's trip count is among them."""
    computations = {
        m.group(1): m.group(2) for m in re.finditer(
            r"^(?:ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", text, re.S | re.M)
    }
    return {
        m.group(1): {int(c) for c in re.findall(
            r"constant\((\d+)\)", computations.get(m.group(2), ""))}
        for m in re.finditer(
            r"%(\S+) = .*? while\(.*?condition=%([^,\s]+)", text)
    }


@pytest.mark.parametrize(
    "images,num_filters",
    [((12544, 32, 32, 3), 10000), ((125000, 24, 24, 3), 512)],
    ids=["cifar-fit", "cifar-krr-fit"],
)
def test_filter_program_holds_no_loop_over_the_sample(
        one_chip, images, num_filters):
    """``build_filters``' one program at both published geometries,
    100,000 sampled windows: it compiles, no ``while`` of it runs a step
    a sampled patch or a step a picked row (the gather it replaced was
    such a loop, 100,000 steps of 2.0-2.3 µs on the chip: PERF.md, PR
    34; the scan over slabs has ten, the SVD's loops 108 at most), and
    its temporaries stay under 3.3 GB, what the old gather asked
    beside the kernel solver's kept models."""
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sample = shape((app.WHITENER_SAMPLE,), jnp.int32)
    compiled = app._filter_bank.lower(
        shape(images), sample, sample, sample,
        shape((num_filters,), jnp.int32), 0.1, size=6,
    ).compile()
    bounds = _while_bounds(compiled.as_text())
    assert any(
        -(-app.WHITENER_SAMPLE // app.GATHER_SLAB) in b for b in bounds.values()
    ), bounds  # the scan over slabs is there
    for name, constants in bounds.items():
        assert not constants & {app.WHITENER_SAMPLE, num_filters}, (
            name, constants)
        assert max(constants, default=0) <= 108, (name, constants)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.3e9


@pytest.mark.parametrize("cell", ["timit-fit", "weighted-bcd-fit"])
def test_gram_products_are_square_fusions_that_read_the_rows(one_chip, cell):
    """The block Gram from its upper block triangle at the published
    shapes — ``_block_stats_gram`` as ``timit-fit`` runs it (65,536 rows of
    16,384 features, a traced start, width 4,096) and the helper alone
    at ``weighted-bcd-fit``'s 327,680 x 4,096 rows: every product under
    the scope is one output fusion with a square ``f32`` result whose
    FIRST operand is the parameter ``X`` (the column slices fuse into
    the products: no row-sized copy, 51 MB of temporaries where a
    materialised half block is 0.5 GB and 2.7 GB), so the benchmark's
    ``gram_roofline_pct.fit`` takes all fifteen or, in a program without
    them, none (a pattern that took a part of the work read 829% in PR
    32). ``as_text()`` leaves operand types out, which the trace's names
    carry: the pattern is held against the line with X's type put back."""
    import json
    import os

    from keystone_tpu.ops.learning import block_ls

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    if cell == "timit-fit":
        n, d, b, scope = 65536, 16384, 4096, "solver.gram"
        lowered = block_ls._block_stats_gram.lower(
            shape((n, d)), shape((d,)), shape((), jnp.int32), width=b, n=n)
    else:
        n, d, b, scope = 327680, 4096, 4096, "wls.stats"

        def gram(X):
            with jax.named_scope(scope):
                return block_ls._sym_gram(X)

        lowered = jax.jit(gram).lower(shape((n, d)))
    compiled = lowered.compile()
    # the one full product needs no temporary at either shape
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    products = [line.strip() for line in entry.splitlines()
                if scope + "/dot_general" in line
                and re.search(r" (fusion|convolution)\(", line)]
    sizes = []
    for op in products:
        found = re.match(r"%\S+ = f32\[(\d+),(\d+)\]\S* fusion\(%X\.", op)
        assert found and found.group(1) == found.group(2), op[:200]
        sizes.append(int(found.group(1)))
    assert sorted(sizes) == [512] * 12 + [1024] * 2 + [2048]
    assert sum(s * s for s in sizes) == block_ls._gram_pairs(b)[0]
    with open(os.path.join(
            os.path.dirname(__file__), "..", "..", "benchmark", "metrics",
            "gram_roofline_pct.fit.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    typed = f"fusion(f32[{n},{d}]{{1,0:T(8,128)}} %X."
    assert all(pattern.search(op.replace("fusion(%X.", typed))
               for op in products)


def _metric_args(name):
    import json
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "benchmark", "metrics",
        name + ".json")
    with open(path) as f:
        return json.load(f)["args"]


@pytest.fixture
def mosaic(monkeypatch):
    """The GMM statistics kernel compiled by Mosaic although jax's
    default backend here is the CPU."""
    from keystone_tpu.ops.images import fv_pallas

    monkeypatch.setattr(
        fv_pallas, "auto_interpret", lambda interpret=None: False)


def test_fisher_kernel_at_the_published_widths_and_its_roofline_pattern(
        one_chip, mosaic):
    """VOCSIFTFisher's Fisher-vector node over a chunk of 16 images'
    74,000 reduced descriptors (d 80, k 256), as a shape group runs it:
    Mosaic takes the kernel with the descriptors as they are (no padded
    or transposed copy of them in the program), and the benchmark's
    ``fv_roofline_pct.vfit`` takes exactly its one custom call, and
    ``fv_device_ms_per_image.vfit`` the program by its name."""
    from keystone_tpu.ops.images.fisher_vector import _FisherRows
    from keystone_tpu.parallel import chunks

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    compiled = chunks.rows_program(_FisherRows(True, 1e-4)).lower(
        (shape(80, 256), shape(80, 256), shape(256)), shape(16, 80, 74000)
    ).compile()
    text = compiled.as_text()
    pattern = _metric_args("fv_roofline_pct.vfit")["pattern"]
    taken = [line.strip() for line in text.splitlines()
             if re.search(pattern, line.strip())]
    assert len(taken) == 1 and "tpu_custom_call" in taken[0], taken
    assert text.count("tpu_custom_call") == 1
    # nothing of the descriptors' size but the argument itself
    assert not re.search(r"= f32\[16,\d+,7\d{4}\]\S* (?!parameter)", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    module = _metric_args("fv_device_ms_per_image.vfit")["pattern"]
    assert re.search(module, text.splitlines()[0]), text.splitlines()[0]


def test_em_program_at_the_published_sample_and_the_gmm_metrics_patterns(
        one_chip, mosaic):
    """The GMM's EM over 999,722 x 80 at 256 words as one device program:
    the kernel is in the loop under the name ``gmm.estep`` (so the Fisher
    kernel's roofline metric does not take it), no (n, k) array of
    posteriors or likelihoods is anywhere in the program, and
    ``gmm_device_ms_per_fit.vfit`` finds the program, and the k-means++
    start, by name."""
    from keystone_tpu.ops.learning import gmm

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    em = gmm._gmm_em.lower(
        shape(80, 999722), shape(256, 80), shape(256, 80), shape(256),
        shape(80), shape(3), max_iterations=100).compile()
    text = em.as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1 and calls[0].startswith("%gmm.estep"), calls
    fv = _metric_args("fv_roofline_pct.vfit")["pattern"]
    assert not [line for line in text.splitlines() if re.search(fv, line)]
    assert not re.search(r"f32\[(999722|1000448),256\]", text)
    assert not re.search(r"f32\[256,(999722|1000448)\]", text)
    assert em.memory_analysis().temp_size_in_bytes < 64 << 20
    init = gmm._gmm_init.lower(
        shape(80, 999722), shape(dtype=jnp.int32), shape(255),
        shape(255, dtype=jnp.int32), shape(2), k=256).compile()
    assert init.as_text().count("tpu_custom_call") == 2  # the hard passes
    named = re.compile(_metric_args("gmm_device_ms_per_fit.vfit")["pattern"])
    for program in (em, init):
        assert named.search(program.as_text().splitlines()[0])


def test_sift_and_projection_of_a_voc_chunk_fit_beside_the_cache(one_chip):
    """Dense SIFT and the PCA projection over 16 images of 500 x 375, the
    programs a shape group's chunk runs (16 is what
    ``chunks.PROGRAM_BYTES`` plans for these images): each compiles (the
    binning kernel by Mosaic at this image's tiles) and holds under 2 GiB
    of temporaries, over which a v5e computed wrong descriptors (PERF.md
    section 6, PR 37)."""
    from keystone_tpu.ops.images import pallas_kernels
    from keystone_tpu.ops.images.sift import _SiftRows
    from keystone_tpu.ops.learning.pca import _project_columns
    from keystone_tpu.parallel import chunks

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    compile_kernel = pallas_kernels.auto_interpret
    pallas_kernels.auto_interpret = lambda interpret=None: False
    try:
        sift = chunks.rows_program(_SiftRows(3, 4, 4, 0)).lower(
            (), shape(16, 375, 500, 1)).compile()
    finally:
        pallas_kernels.auto_interpret = compile_kernel
    assert sift.as_text().count("tpu_custom_call") >= 4  # one a scale
    out = sift.memory_analysis().output_size_in_bytes
    assert 16 * 128 * 70000 * 4 < out < 16 * 128 * 76000 * 4
    assert sift.memory_analysis().temp_size_in_bytes < chunks.PROGRAM_BYTES
    assert re.search(
        _metric_args("sift_device_ms_per_image.vfit")["pattern"],
        sift.as_text().splitlines()[0])
    project = chunks.rows_program(_project_columns).lower(
        shape(128, 80), shape(16, 128, 74000)).compile()
    assert project.memory_analysis().temp_size_in_bytes < 1e9
