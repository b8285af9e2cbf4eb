"""Stats node tests (reference suites: nodes/stats/*Suite.scala)."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.ops.stats import (
    ColumnSampler,
    CosineRandomFeatures,
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
    Sampler,
    SignedHellingerMapper,
    StandardScaler,
    TermFrequency,
)
from keystone_tpu.parallel.dataset import Dataset


def test_term_frequency_reference_suite_fixtures():
    """Port of TermFrequencySuite (nodes/misc/TermFrequencySuite.scala):
    simple strings, mixed hashable types (ngram tuples + ints), and the
    log-weighted variant."""
    import math

    out = TermFrequency().apply(["b", "a", "c", "b", "b", "a", "b"])
    assert out == {"a": 2, "b": 4, "c": 1}

    mixed = ["b", "a", "c", ("b", "b"), ("b", "b"), 12, 12, "a", "b", 12]
    out = TermFrequency().apply(mixed)
    assert out == {"a": 2, "b": 2, "c": 1, ("b", "b"): 2, 12: 3}

    out = TermFrequency(lambda x: math.log(x + 1)).apply(
        ["b", "a", "c", "b", "b", "a", "b"]
    )
    assert out == {
        "a": math.log(3), "b": math.log(5), "c": math.log(2),
    }


def test_random_sign_node_involution():
    node = RandomSignNode.create(16, seed=3)
    x = np.random.default_rng(0).standard_normal((5, 16)).astype(np.float32)
    out = np.asarray(node.apply_batch(Dataset.of(x)).array())
    # applying signs twice recovers the input
    again = np.asarray(node.apply_batch(Dataset.of(out)).array())
    np.testing.assert_allclose(again, x, rtol=1e-6)
    assert set(np.unique(np.asarray(node.signs))) <= {-1.0, 1.0}


def test_padded_fft_matches_numpy():
    x = np.random.default_rng(1).standard_normal((3, 10)).astype(np.float32)
    out = np.asarray(PaddedFFT().apply_batch(Dataset.of(x)).array())
    pad = 16
    expect = np.real(np.fft.fft(np.pad(x, ((0, 0), (0, pad - 10)))))[:, :8]
    np.testing.assert_allclose(out, expect, atol=1e-4)
    assert out.shape == (3, 8)


def test_linear_rectifier():
    x = np.array([[-1.0, 0.5, 2.0]], np.float32)
    out = np.asarray(
        LinearRectifier(0.0, 0.25).apply_batch(Dataset.of(x)).array()
    )
    np.testing.assert_allclose(out, [[0.0, 0.25, 1.75]])


def test_normalize_rows():
    x = np.random.default_rng(2).standard_normal((4, 7)).astype(np.float32)
    out = np.asarray(NormalizeRows().apply_batch(Dataset.of(x)).array())
    np.testing.assert_allclose(
        np.linalg.norm(out, axis=1), np.ones(4), rtol=1e-5
    )


def test_signed_hellinger():
    x = np.array([[-4.0, 9.0, 0.0]], np.float32)
    out = np.asarray(
        SignedHellingerMapper().apply_batch(Dataset.of(x)).array()
    )
    np.testing.assert_allclose(out, [[-2.0, 3.0, 0.0]])


def test_standard_scaler_stats(mesh8):
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((100, 5)) * 3 + 7).astype(np.float32)
    ds = Dataset.of(x).shard()
    model = StandardScaler().fit(ds)
    np.testing.assert_allclose(np.asarray(model.mean), x.mean(0), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(model.std), x.std(0, ddof=1), rtol=1e-3
    )
    out = np.asarray(model.apply_batch(ds).array())
    np.testing.assert_allclose(out.mean(0), np.zeros(5), atol=1e-4)
    np.testing.assert_allclose(out.std(0, ddof=1), np.ones(5), rtol=1e-3)


@pytest.mark.skipif(
    len(jax.devices()) < 8, reason='needs 8 data shards'
)
def test_standard_scaler_respects_padding(mesh8):
    # 10 valid rows sharded 8 ways -> padded to 16; stats must use n=10
    x = np.ones((10, 3), np.float32) * 5
    ds = Dataset.of(x).shard()
    assert ds.padded_n == 16
    model = StandardScaler(normalize_std_dev=False).fit(ds)
    np.testing.assert_allclose(np.asarray(model.mean), [5, 5, 5], rtol=1e-6)
    out = model.apply_batch(ds)
    # padding rows stay zero after centering
    assert np.allclose(np.asarray(out.padded())[10:], 0.0)


def test_cosine_random_features_shape_and_range():
    node = CosineRandomFeatures.create(d=6, num_features=32, gamma=0.5, seed=0)
    x = np.random.default_rng(4).standard_normal((9, 6)).astype(np.float32)
    out = np.asarray(node.apply_batch(Dataset.of(x)).array())
    assert out.shape == (9, 32)
    assert np.all(out <= 1.0) and np.all(out >= -1.0)
    single = np.asarray(node.apply(jnp.asarray(x[0])))
    np.testing.assert_allclose(out[0], single, atol=1e-5)


def test_column_sampler_and_sampler():
    mats = [np.random.default_rng(i).standard_normal((4, 20)) for i in range(3)]
    out = ColumnSampler(5, seed=0).apply_batch(Dataset.from_items(mats))
    assert all(np.asarray(m).shape == (4, 5) for m in out.items())
    ds = Sampler(10, seed=0).apply(np.arange(100.0).reshape(50, 2))
    assert ds.n == 10


def test_random_fft_features_matches_composed_branches():
    """Fused RandomFFTFeatures == gather of RandomSignNode -> PaddedFFT ->
    LinearRectifier branches, feature for feature."""
    from keystone_tpu.ops.stats import (
        LinearRectifier, PaddedFFT, RandomFFTFeatures, RandomSignNode,
    )

    rng = np.random.default_rng(0)
    d, f, n = 100, 3, 17
    x = rng.standard_normal((n, d)).astype(np.float32)
    ds = Dataset.from_array(jnp.asarray(x))

    fused = RandomFFTFeatures.create(d, f, seed=5)
    got = np.asarray(fused.apply_batch(ds).padded())

    parts = []
    for i in range(f):
        b = LinearRectifier(0.0).apply_batch(
            PaddedFFT().apply_batch(
                RandomSignNode.create(d, seed=5 + i).apply_batch(ds)
            )
        )
        parts.append(np.asarray(b.padded()))
    want = np.concatenate(parts, axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert fused.out_dim == want.shape[1]
    # single-example apply agrees with the batch path
    np.testing.assert_allclose(
        np.asarray(fused.apply(jnp.asarray(x[0]))), want[0],
        rtol=1e-5, atol=1e-5,
    )


def test_random_fft_features_nonzero_threshold_remasks_pad_rows():
    """With rectify_threshold > 0, pad rows must stay exactly zero (the
    Gram-based solvers sum over all padded rows assuming pads are zero),
    and valid rows must match the composed branch path."""
    from keystone_tpu.ops.stats import (
        LinearRectifier, PaddedFFT, RandomFFTFeatures, RandomSignNode,
    )

    rng = np.random.default_rng(1)
    d, f, n, pad_n = 64, 2, 5, 8
    x = np.zeros((pad_n, d), np.float32)
    x[:n] = rng.standard_normal((n, d)).astype(np.float32)
    ds = Dataset.from_array(jnp.asarray(x), n=n)
    thresh = 0.25

    fused = RandomFFTFeatures.create(d, f, seed=3, rectify_threshold=thresh)
    got = np.asarray(fused.apply_batch(ds).padded())
    assert got.shape[0] == pad_n
    np.testing.assert_array_equal(got[n:], 0.0)

    parts = []
    for i in range(f):
        b = LinearRectifier(thresh).apply_batch(
            PaddedFFT().apply_batch(
                RandomSignNode.create(d, seed=3 + i).apply_batch(ds)
            )
        )
        parts.append(np.asarray(b.padded()))
    want = np.concatenate(parts, axis=1)
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-5, atol=1e-5)


def _digit_like(n: int, seed: int) -> np.ndarray:
    """(n, 784) float32 in 0..255, a fifth of the pixels inked."""
    rng = np.random.default_rng(seed)
    ink = rng.random((n, 784)) < 0.2
    return np.round(ink * rng.random((n, 784)) * 255.0).astype(np.float32)


def _fft_bank_f64(x: np.ndarray, num_ffts: int, seed: int) -> np.ndarray:
    """The branches by their definition, in float64: the signs of
    ``default_rng(seed + i)``, zero-padded to 1,024, numpy's FFT, the
    real parts of the first 512 coefficients, rectified."""
    out = []
    for i in range(num_ffts):
        s = np.random.default_rng(seed + i).integers(0, 2, size=784) * 2.0 - 1
        spec = np.fft.fft(np.asarray(x, np.float64) * s, n=1024, axis=1)
        out.append(np.maximum(spec.real[:, :512], 0.0))
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("path", ["batch", "one_row"])
def test_random_fft_bank_matches_float64_fft(path):
    """6 FFTs over 300 rows against the float64 FFT: the batch path's
    product with the signed cosine basis, and one row's product of its
    signed copies with the cosines, both at HIGHEST, keep float32's
    precision."""
    from keystone_tpu.ops.stats import RandomFFTFeatures

    x = _digit_like(300, 4)
    node = RandomFFTFeatures.create(784, 6, seed=21)
    if path == "batch":
        got = node.apply_batch(Dataset.from_array(jnp.asarray(x))).padded()
    else:
        x = x[:12]
        one = jax.jit(node.apply)
        got = np.stack([np.asarray(one(jnp.asarray(row))) for row in x])
    got = np.asarray(got, np.float64)
    want = _fft_bank_f64(x, 6, 21)
    assert got.shape == (x.shape[0], 6 * 512)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


@pytest.mark.parametrize("thresh", [0.0, 0.25])
def test_random_fft_one_row_is_the_batch_row(thresh):
    """A row scored alone (no basis formed) is the batch path's row for
    that row, to float32's rounding, at either threshold."""
    from keystone_tpu.ops.stats import RandomFFTFeatures

    x = _digit_like(4, 9)
    node = RandomFFTFeatures.create(784, 3, seed=4, rectify_threshold=thresh)
    batch = np.asarray(node.apply_batch(
        Dataset.from_array(jnp.asarray(x))).padded())
    for i in range(4):
        row = np.asarray(node.apply(jnp.asarray(x[i])))
        assert np.all(row >= thresh)
        np.testing.assert_allclose(row, batch[i], rtol=1e-5,
                                   atol=1e-5 * np.abs(batch[i]).max())


@pytest.mark.parametrize("thresh", [0.0, 0.25])
def test_random_fft_bank_pad_rows_stay_zero(thresh):
    """Pad rows of a Dataset (n of padded_n valid) come out exactly zero
    from the bank at any threshold, and the rows and the span and
    counter of the batch path are left behind."""
    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.observability.tracing import (
        disable_tracing, enable_tracing,
    )
    from keystone_tpu.ops.stats import RandomFFTFeatures

    x = np.zeros((16, 784), np.float32)
    x[:11] = _digit_like(11, 6)
    node = RandomFFTFeatures.create(784, 3, seed=2, rectify_threshold=thresh)
    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        out = node.apply_batch(Dataset.from_array(jnp.asarray(x), n=11))
        names = [s.name for s in tr.recent()]
        rows = get_global_registry().counter(
            "keystone_featurize_fft_rows_total").get()
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()
    got = np.asarray(out.padded())
    assert got.shape == (16, 3 * 512) and out.n == 11
    np.testing.assert_array_equal(got[11:], 0.0)
    assert np.all(got[:11] >= thresh)
    assert names.count("fft.bank") == 1 and rows == 11


@pytest.mark.parametrize("case", ["array", "ragged_items", "noted_node"])
def test_column_sampler_on_the_device_draws_what_the_loop_drew(case):
    """A batch of matrices sampled on the device — one array, ragged host
    items (one array a shape), or shape groups with a node noted on them
    that runs a chunk at a time — gives, item for item, the columns the
    per-item loop it replaces draws for the same seed
    (``default_rng((seed, i))`` for item i), in the items' order; and it
    leaves the sampler's span and counter behind."""
    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.observability.tracing import (
        disable_tracing, enable_tracing,
    )
    from keystone_tpu.workflow.api import Transformer

    class Twice(Transformer):
        def apply(self, m):
            return 2.0 * m

        def rowwise(self):
            return _twice, ()

    rng = np.random.default_rng(0)
    widths = [20] * 7 if case == "array" else [20, 31, 20, 17, 31, 20, 20]
    mats = [rng.standard_normal((4, m)).astype(np.float32) for m in widths]
    loop = ColumnSampler(6, seed=3)
    scale = 2.0 if case == "noted_node" else 1.0
    want = [np.asarray(loop.apply(scale * m)) for m in mats]
    if case == "array":
        ds = Dataset.from_array(jnp.asarray(np.stack(mats)))
    else:
        ds = Dataset.from_items(mats)
        if case == "noted_node":
            ds = Twice().batch_transform([ds.grouped()])
            assert ds.is_grouped and ds._steps  # noted, not yet run
    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        out = ColumnSampler(6, seed=3).apply_batch(ds)
        names = [s.name for s in tr.recent()]
        sampled = sum(
            s.value for f in get_global_registry().collect()
            if f.name == "keystone_sampled_columns_total"
            for s in f.samples if s.suffix == "")
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()
    assert out.is_array and out.padded().shape == (7, 4, 6)
    for got, w in zip(np.asarray(out.array()), want):
        np.testing.assert_array_equal(got, w)
    assert names.count("stats.column_sample") == 1
    assert sampled == 7 * 6


def _twice(arrays, x):
    del arrays
    return 2.0 * x
