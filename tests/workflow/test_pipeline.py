"""Pipeline API semantics (modeled on the reference PipelineSuite):
chaining, laziness, gather, the fit-once memoization guarantee, fitted
pipeline save/load."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow import (
    Estimator,
    LabelEstimator,
    Pipeline,
    PipelineEnv,
    Transformer,
)
from keystone_tpu.ops.util import VectorCombiner


@dataclasses.dataclass(eq=False)
class Scale(Transformer):
    factor: float

    def apply(self, x):
        return x * self.factor


@dataclasses.dataclass(eq=False)
class AddConst(Transformer):
    c: float

    def apply(self, x):
        return x + self.c


class MeanCenterEstimator(Estimator):
    def __init__(self):
        self.fit_count = 0

    def fit(self, data: Dataset) -> Transformer:
        self.fit_count += 1
        mean = jnp.mean(data.array(), axis=0)
        return AddConst(-mean)


class OffsetLabelEstimator(LabelEstimator):
    def __init__(self):
        self.fit_count = 0

    def fit(self, data: Dataset, labels: Dataset) -> Transformer:
        self.fit_count += 1
        delta = jnp.mean(labels.array() - data.array())
        return AddConst(delta)


def test_transformer_single_and_batch():
    t = Scale(2.0)
    out = t.to_pipeline().apply_datum(jnp.asarray([1.0, 2.0])).get()
    np.testing.assert_allclose(out, [2.0, 4.0])
    ds = Dataset.from_array(jnp.ones((4, 3)))
    out = t(ds).get()
    np.testing.assert_allclose(np.asarray(out.array()), 2 * np.ones((4, 3)))


def test_chaining():
    pipe = Scale(2.0).and_then(AddConst(1.0)).and_then(Scale(10.0))
    out = pipe.apply_datum(jnp.asarray([1.0])).get()
    np.testing.assert_allclose(out, [30.0])


def test_estimator_chaining_and_laziness():
    data = Dataset.from_array(jnp.asarray([[1.0], [3.0]]))  # mean 2
    est = MeanCenterEstimator()
    pipe = Scale(1.0).and_then(est, data)
    assert est.fit_count == 0  # nothing executed yet
    out = pipe.apply_datum(jnp.asarray([5.0]))
    assert est.fit_count == 0  # still lazy
    np.testing.assert_allclose(out.get(), [3.0])
    assert est.fit_count == 1


def test_fit_once_guarantee():
    """Reference PipelineSuite 'Do not fit estimators multiple times'."""
    data = Dataset.from_array(jnp.asarray([[1.0], [3.0]]))
    est = MeanCenterEstimator()
    pipe = Scale(1.0).and_then(est, data)
    a = pipe.apply_datum(jnp.asarray([5.0]))
    a.get()
    # A *new* pipeline built from the same estimator + data shares the prefix
    pipe2 = Scale(1.0).and_then(est, data)
    b = pipe2.apply_datum(jnp.asarray([7.0]))
    np.testing.assert_allclose(b.get(), [5.0])
    assert est.fit_count == 1  # memoized via PipelineEnv prefix state


def test_label_estimator():
    data = Dataset.from_array(jnp.zeros((3, 1)))
    labels = Dataset.from_array(jnp.ones((3, 1)))
    est = OffsetLabelEstimator()
    pipe = Scale(1.0).and_then(est, data, labels)
    out = pipe.apply_datum(jnp.asarray([0.5])).get()
    np.testing.assert_allclose(out, [1.5])
    assert est.fit_count == 1


def test_gather_and_combine():
    branches = [Scale(1.0), Scale(2.0), Scale(3.0)]
    pipe = Pipeline.gather(branches).and_then(VectorCombiner())
    ds = Dataset.from_array(jnp.ones((2, 2)))
    out = pipe(ds).get()
    np.testing.assert_allclose(
        np.asarray(out.array()),
        [[1, 1, 2, 2, 3, 3], [1, 1, 2, 2, 3, 3]],
    )
    single = pipe.apply_datum(jnp.ones((2,))).get()
    np.testing.assert_allclose(single, [1, 1, 2, 2, 3, 3])


def test_fit_returns_frozen_pipeline(tmp_path):
    data = Dataset.from_array(jnp.asarray([[2.0], [4.0]]))  # mean 3
    est = MeanCenterEstimator()
    pipe = Scale(1.0).and_then(est, data)
    fitted = pipe.fit()
    assert est.fit_count == 1
    np.testing.assert_allclose(fitted.apply(jnp.asarray([4.0])), [1.0])
    # batch apply
    out = fitted.apply(Dataset.from_array(jnp.asarray([[3.0], [6.0]])))
    np.testing.assert_allclose(np.asarray(out.array()), [[0.0], [3.0]])
    # fitting again doesn't refit
    pipe.fit()
    assert est.fit_count == 1
    # save/load
    p = tmp_path / "fitted.pkl"
    fitted.save(str(p))
    from keystone_tpu.workflow import FittedPipeline

    loaded = FittedPipeline.load(str(p))
    np.testing.assert_allclose(loaded.apply(jnp.asarray([4.0])), [1.0])


def test_fitted_pipeline_jit():
    pipe = Scale(2.0).and_then(AddConst(1.0))
    # a transformer-only pipeline is fit-able without estimators
    fitted = pipe.fit()
    f = fitted.jit()
    np.testing.assert_allclose(f(jnp.asarray([1.0, 2.0])), [3.0, 5.0])


def test_cse_merges_equal_branches():
    """Two structurally equal dataclass transformers merge (CSE)."""
    from keystone_tpu.workflow.executor import GraphExecutor

    pipe = Pipeline.gather([Scale(2.0), Scale(2.0)])
    ds = Dataset.from_array(jnp.ones((2, 1)))
    result = pipe(ds)
    result.get()
    optimized = result._executor.graph
    # gather + one merged Scale + data node = 3 operators
    assert len(optimized.operators) == 3


def test_unexecutable_source_dependent():
    pipe = Scale(2.0).to_pipeline()
    with pytest.raises(ValueError):
        pipe.executor.execute(pipe.sink)


def test_apply_pipeline_dataset_chains_lazily():
    data = Dataset.from_array(jnp.ones((2, 2)))
    stage1 = Scale(3.0)(data)  # PipelineDataset
    stage2 = AddConst(1.0)(stage1)
    out = stage2.get()
    np.testing.assert_allclose(np.asarray(out.array()), 4 * np.ones((2, 2)))


def test_incremental_extension_reuses_executed_prefix():
    """Reference PipelineSuite 'Incrementally update execution state':
    extending an already-executed pipeline with and_then must not refit
    the earlier estimator — its prefix is already in PipelineEnv state."""
    data = Dataset.from_array(jnp.asarray([[1.0], [3.0]]))
    est = MeanCenterEstimator()
    pipe = Scale(1.0).and_then(est, data)
    pipe.apply_datum(jnp.asarray([5.0])).get()
    assert est.fit_count == 1

    extended = pipe.and_then(Scale(10.0))
    out = extended.apply_datum(jnp.asarray([5.0])).get()
    np.testing.assert_allclose(out, [30.0])
    assert est.fit_count == 1  # prefix reused, not refit


def test_incremental_extension_with_label_estimator():
    data = Dataset.from_array(jnp.zeros((3, 1)))
    labels = Dataset.from_array(jnp.ones((3, 1)))
    est = OffsetLabelEstimator()
    pipe = Scale(1.0).and_then(est, data, labels)
    pipe.apply_datum(jnp.asarray([0.0])).get()

    extended = pipe.and_then(AddConst(5.0))
    out = extended.apply_datum(jnp.asarray([0.0])).get()
    np.testing.assert_allclose(out, [6.0])
    assert est.fit_count == 1


def test_incremental_second_estimator_fits_on_first_output():
    """Chaining a SECOND estimator whose training data flows through the
    first: the first stays fit-once, the second sees transformed data."""
    data = Dataset.from_array(jnp.asarray([[2.0], [4.0]]))
    est1 = MeanCenterEstimator()
    pipe = Scale(1.0).and_then(est1, data)
    pipe.apply_datum(jnp.asarray([1.0])).get()

    est2 = MeanCenterEstimator()
    # est2 trains on est1's OUTPUT of the same data (mean 0 after
    # centering), so its learned offset is 0
    extended = pipe.and_then(est2, data)
    out = extended.apply_datum(jnp.asarray([1.0])).get()
    np.testing.assert_allclose(out, [-2.0])  # 1 - mean(3) + 0
    assert est1.fit_count == 1
    assert est2.fit_count == 1


def test_fitted_pipeline_jit_batch_matches_executor():
    """jit_batch lowers the WHOLE fitted transformer graph into one
    compiled program (SURVEY §7 staging); it must match the node-by-node
    executor path on an array-mode chain, including a gather join."""
    import jax.numpy as jnp

    from keystone_tpu.ops.stats import (
        LinearRectifier, NormalizeRows, RandomSignNode,
    )
    from keystone_tpu.ops.util.nodes import VectorCombiner
    from keystone_tpu.workflow.api import Pipeline

    branches = [
        RandomSignNode.create(12, seed=i)
        .and_then(LinearRectifier(0.0))
        .and_then(NormalizeRows())
        for i in range(2)
    ]
    pipe = Pipeline.gather(branches).and_then(VectorCombiner())
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((6, 12)).astype(np.float32)
    )
    ref = pipe.apply(Dataset.from_array(x)).get().padded()
    out = pipe.fit().jit_batch()(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_bucketed_batch_chunks_large_shape_groups(monkeypatch):
    """Ragged items become one array a shape, and a group too large for
    what the device has free goes through in chunks whose rows follow
    from bytes (the last one starting a chunk before the end, so the
    group compiles once): results equal the per-item apply, in order, no
    program sees more than a chunk, and nothing is cut into items until
    items are asked for."""
    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.parallel import chunks

    rng = np.random.default_rng(0)
    # 9 items of one shape interleaved with 4 of another
    items = [
        rng.standard_normal((3, 5) if i % 4 else (2, 7)).astype(np.float32)
        for i in range(13)
    ]
    # a row of (3, 5) in, (5,) out holds 20 bytes across the run: room
    # for 4 rows (half of what is free once the 9 x 20 result is out)
    monkeypatch.setattr(
        chunks, "device_free_bytes", lambda batch: 9 * 20 + 2 * 4 * 20)
    seen = []
    real = chunks.take_chunk

    def spy(fns, chunk_rows, *rest):
        seen.append(chunk_rows)
        return real(fns, chunk_rows, *rest)

    monkeypatch.setattr(chunks, "take_chunk", spy)
    monkeypatch.setattr(chunks, "_planned", {})

    def count(name):
        return sum(
            s.value for f in get_global_registry().collect()
            if f.name == name for s in f.samples if s.suffix == "")

    reset_global_registry()
    try:
        out = _row_sums([])._bucketed_batch(Dataset.from_items(items))
        assert out.is_grouped and not out.is_array
        groups = out.groups()
        assert sorted(b.shape for _, b in groups) == [(4, 7), (9, 5)]
        assert sorted(seen) == [4, 4, 4, 4]  # 9 rows: 3 chunks; 4 fit whole
        assert count("keystone_workflow_shape_groups_total") == 2
        assert count("keystone_workflow_chunks_total") == 4
        assert count("keystone_workflow_array_items_total") == 13
        assert count("keystone_workflow_item_slices_total") == 0
        got = out.items()
        assert count("keystone_workflow_item_slices_total") == 13
    finally:
        reset_global_registry()
    assert len(got) == 13
    for x, y in zip(items, got):
        np.testing.assert_allclose(np.asarray(y), x.sum(0) + 1.0, rtol=1e-6)


def _row_sums(seen, pytree=False):
    class RowSums(Transformer):
        vmap_batch = False
        bucket_vmap = True

        def apply(self, x):
            y = jnp.sum(x, axis=0) + 1.0
            return (y, {"twice": 2.0 * x}) if pytree else y

        def _jitted_vmap(self):
            fn = super()._jitted_vmap()

            def spy(batch):
                seen.append(batch.shape)
                return fn(batch)

            return spy

    return RowSums()


@pytest.mark.parametrize(
    "case",
    [
        "host_items", "device_items", "array_over_chunk",
        "array_with_pad_rows", "array_under_chunk", "ragged_items",
        "pytree_apply", "traced_array",
    ],
)
def test_bucket_vmap_keeps_one_shape_an_array(monkeypatch, case):
    """A bucket_vmap node handed items of one shape, or an array, works
    on slices of one array, a chunk a dispatch, and returns array mode
    with no pad rows of its own; ragged items come back as one array a
    shape; inside jit the whole input is one vmap call. Results equal the per-item
    apply, in order."""
    import jax

    from keystone_tpu.workflow import api

    monkeypatch.setattr(api, "BUCKET_CHUNK", 4)
    seen = []
    node = _row_sums(seen, pytree=case == "pytree_apply")
    rng = np.random.default_rng(0)
    n = 3 if case == "array_under_chunk" else 9
    items = [
        rng.standard_normal(
            (2, 7) if case == "ragged_items" and i % 4 == 0 else (3, 5)
        ).astype(np.float32)
        for i in range(n)
    ]
    rows = n
    if case in ("array_over_chunk", "array_under_chunk"):
        out = node.apply_batch(Dataset.from_array(jnp.asarray(items)))
    elif case == "array_with_pad_rows":
        rows = n + 1  # a zero row past n, as Dataset.shard leaves them
        x = jnp.concatenate([jnp.asarray(items), jnp.zeros((1, 3, 5))])
        out = node.apply_batch(Dataset.from_array(x, n=n))
    elif case == "traced_array":
        out = Dataset.from_array(jax.jit(
            lambda x: node.apply_batch(Dataset.from_array(x)).padded()
        )(jnp.asarray(items)))
    elif case == "device_items":
        out = node.apply_batch(
            Dataset.from_items([jnp.asarray(x) for x in items])
        )
    else:
        out = node.apply_batch(Dataset.from_items(items))
    assert out.n == n
    if case == "ragged_items":
        assert not out.is_array and out.is_grouped
        assert sorted(b.shape for _, b in out.groups()) == [(3, 7), (6, 5)]
    else:
        assert out.is_array and out.padded_n == rows
        assert [s[0] for s in seen] == {
            "array_under_chunk": [3], "traced_array": [9],
        }.get(case, [4, 4, 4])
    got = out.items()
    assert len(got) == n
    for x, y in zip(items, got):
        if case == "pytree_apply":
            y, extra = y
            np.testing.assert_allclose(extra["twice"], 2.0 * x, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y), x.sum(0) + 1.0, rtol=1e-6)


def test_flagship_featurizer_never_leaves_array_mode():
    """The flagship featurizer on host images of one shape: every node
    takes its array branch (no per-item map, no restacking of items) and
    the features equal the per-image apply."""
    from keystone_tpu.observability.tracing import (
        disable_tracing, enable_tracing,
    )
    from keystone_tpu.serving.featurize import flagship_pipeline

    pipe = flagship_pipeline(
        np.random.default_rng(3), 8, 4, sift_step=4, sift_scales=2
    )
    images = [
        np.random.default_rng(i).integers(0, 256, (64, 64, 3), np.uint8)
        for i in range(3)
    ]
    want = np.stack(
        [np.asarray(pipe.apply_datum(img).get()) for img in images]
    )
    tr = enable_tracing()
    tr.clear()
    try:
        out = pipe(Dataset.from_items(images)).get()
        names = {s.name for s in tr.recent()}
    finally:
        disable_tracing()
        tr.clear()
    assert out.is_array and out.padded_n == 3
    # float32 rounding moves a few SIFT bins across the quantiser's
    # floor(512 d): 1e-5 of the largest feature, not more
    np.testing.assert_allclose(
        np.asarray(out.array()), want, rtol=1e-4, atol=1e-5
    )
    assert "workflow.apply" in names
    assert not names & {"workflow.map_items", "workflow.to_array"}


def test_shape_group_chunks_stay_under_a_program_s_bytes(monkeypatch):
    """A shape group's chunk never holds more than ``PROGRAM_BYTES`` in
    flight (rows in, every function's output), whatever the device has
    free, and a reader of groups that have nothing noted on them gets
    them in such chunks too; results are the per-item apply's, in order."""
    from keystone_tpu.parallel import chunks

    monkeypatch.setattr(chunks, "_planned", {})
    # a (3, 5) row in and a (5,) row out are 80 bytes: room for 4 rows
    monkeypatch.setattr(chunks, "PROGRAM_BYTES", 4 * 80)
    assert chunks.rows_that_fit(9, 20, 0, None, most_rows=5) == 4
    assert chunks.rows_that_fit(9, 20, 0, None) == 9
    assert chunks.rows_that_fit(4, 20, 0, 10 ** 9, most_rows=4) == 4
    assert chunks.rows_a_chunk(9, 20, 0, 2 * 4 * 20) == 3
    rng = np.random.default_rng(1)
    items = [rng.standard_normal((3, 5)).astype(np.float32) for _ in range(9)]
    out = _row_sums([])._bucketed_batch(
        Dataset.from_groups([(np.arange(9), jnp.asarray(np.stack(items)))]))
    parts = list(out.chunks())
    assert [len(p) for p, _ in parts] == [4, 4, 1]
    for (places, got) in parts:
        for i, y in zip(places, np.asarray(got)):
            np.testing.assert_allclose(y, items[int(i)].sum(0) + 1.0, rtol=1e-6)
    # nothing noted: the rows themselves, 60 bytes each, 5 fit, 4 a chunk
    plain = Dataset.from_groups([(np.arange(9), jnp.asarray(np.stack(items)))])
    sizes = [len(p) for p, _ in plain.chunks()]
    assert sizes == [4, 4, 1] and sum(sizes) == 9


def test_a_shape_s_chunk_is_kept_for_its_later_groups(monkeypatch):
    """The chunk planned when rows of a shape first come through a run is
    the shape's: a later group of fewer rows is filled up to it and runs
    the same programs, only its own rows coming back (and the rows
    computed beside them counted); a group of more rows than the shape
    has had, with room for a larger chunk, plans the shape again; what
    is free at a later moment does not (on a v5e it counted the chunks
    dispatched ahead and compiled new programs inside a timed fit)."""
    from keystone_tpu.observability.registry import (
        get_global_registry, reset_global_registry,
    )
    from keystone_tpu.parallel import chunks

    monkeypatch.setattr(chunks, "_planned", {})
    monkeypatch.setattr(chunks, "PROGRAM_BYTES", 4 * 80)  # 4 rows in flight
    free = {"bytes": 10 ** 9}
    monkeypatch.setattr(chunks, "device_free_bytes", lambda b: free["bytes"])
    seen = []
    real = chunks.take_chunk

    def spy(fns, chunk_rows, *rest):
        seen.append(chunk_rows)
        return real(fns, chunk_rows, *rest)

    monkeypatch.setattr(chunks, "take_chunk", spy)
    rng = np.random.default_rng(2)
    node = _row_sums([])  # one node: a plan is kept by its functions

    def through(n):
        items = [rng.standard_normal((3, 5)).astype(np.float32)
                 for _ in range(n)]
        del seen[:]
        out = node._bucketed_batch(Dataset.from_groups(
            [(np.arange(n), jnp.asarray(np.stack(items)))]))
        got = np.asarray(out.array())
        assert got.shape == (n, 5)
        np.testing.assert_allclose(
            got, np.stack([x.sum(0) + 1.0 for x in items]), rtol=1e-6)
        return list(seen)

    def padded():
        return sum(
            s.value for f in get_global_registry().collect()
            if f.name == "keystone_workflow_padded_rows_total"
            for s in f.samples if s.suffix == "")

    reset_global_registry()
    try:
        assert through(2) == [2]  # the first group of the shape, whole
        assert through(9) == [4, 4, 4]  # room for more: planned again
        before = padded()
        assert through(3) == [4]  # filled up to the shape's chunk
        assert padded() - before == 1
        assert through(7) == [4, 4]  # the last chunk starts at row 3
        free["bytes"] = 7 * 20 + 2 * 2 * 20  # room for 2 rows beside 7 out
        assert through(7) == [4, 4]  # the shape's chunk is kept
        assert through(12) == [4, 4, 4]  # more rows, no larger chunk fits
    finally:
        reset_global_registry()
