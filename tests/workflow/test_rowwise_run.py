"""RowwiseRun / RowwiseRunRule: a run of row-wise nodes goes through in
chunks of rows planned from bytes, and only its last output is whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import (
    disable_tracing,
    enable_tracing,
)
from keystone_tpu.ops.images import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow import api
from keystone_tpu.workflow.api import RowwiseRun, plan_rowwise_run


def window_sum(w):
    return jnp.sum(w, axis=(1, 2))


def chain(filters=8, seed=0, fold=True):
    """Convolver → rectifier → sum pooler → vectorizer. ``fold=False``:
    the pooler sums through a ``pool_fn`` of its own, which the
    Convolver's function does not absorb."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((filters, 6 * 6 * 3)), jnp.float32)
    return [
        Convolver(w, 32, 32, 3, normalize_patches=True),
        SymmetricRectifier(alpha=0.25),
        Pooler(13, 14) if fold else Pooler(13, 14, pool_fn=window_sum),
        ImageVectorizer(),
    ]


def images(rows, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.uniform(0, 255, (rows, 32, 32, 3)).round(), jnp.float32)


def counters():
    out = {}
    for fam in get_global_registry().collect():
        if fam.name.startswith("keystone_workflow_run_"):
            out[fam.name] = sum(s.value for s in fam.samples)
    return out


def free_for(run, batch, chunk_rows):
    """Free bytes under which the plan takes ``chunk_rows`` rows."""
    whole = run.plan(batch, None)
    return whole.out_bytes + 2 * chunk_rows * whole.item_bytes


# rows a multiple of the chunk, a ragged tail, one row more than a chunk,
# fewer rows than a chunk (whole), one row (whole); each with the three
# functions as they are and with the Convolver's function standing for
# three (a folded run the plan calls whole is one chunk of the run)
@pytest.mark.parametrize("fold", [False, True])
@pytest.mark.parametrize("rows,chunk,chunks", [
    (32, 8, 4), (29, 8, 4), (9, 8, 2), (5, 8, 0), (1, 8, 0)])
def test_chunked_run_equals_the_nodes_one_by_one(
        rows, chunk, chunks, fold, monkeypatch):
    nodes = chain(fold=fold)
    run = RowwiseRun(nodes)
    assert run.folded == fold
    x = images(rows)
    want = Dataset.from_array(x)
    for node in nodes:
        want = node.apply_batch(want)
    monkeypatch.setattr(
        api, "_device_free_bytes", lambda batch: free_for(run, x, chunk))
    before = counters()
    got = run.apply_batch(Dataset.from_array(x))
    assert got.is_array and got.n == rows
    if fold:  # the same numbers summed in another order
        np.testing.assert_allclose(
            np.asarray(got.array()), np.asarray(want.array()), rtol=1e-5)
    else:
        # one program a chunk against one program a node: the same
        # operations in the same order on the CPU
        np.testing.assert_array_equal(
            np.asarray(got.array()), np.asarray(want.array()))
    after = counters()
    made = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    plan = run.plan(x, free_for(run, x, chunk))
    assert plan.chunked == (chunks > 0)
    if fold:
        chunks = max(chunks, 1)
    if chunks:
        # the rows divided evenly over the chunks that `chunk` asks for
        assert plan.chunk_rows == -(-rows // chunks) <= chunk
        assert made == {
            "keystone_workflow_run_items_total": rows,
            "keystone_workflow_run_folded_items_total": rows if fold else 0,
            "keystone_workflow_run_chunks_total": chunks,
            "keystone_workflow_run_chunk_bytes_total":
                chunks * plan.chunk_rows * plan.item_bytes,
        }
    else:
        assert not any(made.values())


def test_pad_rows_come_out_zero(monkeypatch):
    run = RowwiseRun(chain())
    x = images(24).at[20:].set(0.0)
    monkeypatch.setattr(
        api, "_device_free_bytes", lambda batch: free_for(run, x, 8))
    got = run.apply_batch(Dataset.from_array(x, n=20))
    assert got.n == 20 and got.padded_n == 24
    assert not np.any(np.asarray(got.padded())[20:])
    assert np.any(np.asarray(got.padded())[19])


def test_spans_once_a_call_and_once_a_chunk(monkeypatch):
    run = RowwiseRun(chain())
    x = images(29)
    monkeypatch.setattr(
        api, "_device_free_bytes", lambda batch: free_for(run, x, 8))
    tracer = enable_tracing()
    tracer.clear()
    try:
        run.apply_batch(Dataset.from_array(x))
        names = [s.name for s in tracer.recent()]
    finally:
        disable_tracing()
    assert names.count("workflow.run") == 1
    assert names.count("workflow.run.chunk") == 4


def test_no_account_of_memory_no_chunks(monkeypatch):
    """The CPU backend keeps no account: the batch goes through whole."""
    run = RowwiseRun(chain())
    x = images(4)
    assert api._device_free_bytes(x) is None
    assert not run.plan(x, api._device_free_bytes(x)).chunked


def parts_of(nodes):
    return tuple(zip(*(node.rowwise() for node in nodes)))


@pytest.mark.parametrize("nodes,folded", [
    # the merged run of the application: the Convolver's function
    # stands for Convolver, rectifier and pooler
    (lambda: chain(), ["_ConvolveRectifyPool", "_vectorize"]),
    # a pooler that sums through its own function, or maps its pixels
    (lambda: chain(fold=False),
     ["_Convolve", "_Rectify", "_Pool", "_vectorize"]),
    (lambda: chain()[:2] + [Pooler(13, 14, pixel_fn=jnp.abs)],
     ["_Convolve", "_Rectify", "_Pool"]),
    # a rectifier alone behind the Convolver; a pooler with no rectifier
    (lambda: chain()[:2], ["_Convolve", "_Rectify"]),
    (lambda: [chain()[0], chain()[2]], ["_Convolve", "_Pool"]),
    # windows more than two deep along an axis
    (lambda: chain()[:2] + [Pooler(2, 6)], ["_Convolve", "_Rectify", "_Pool"]),
    # no Convolver in front: the run of a second reader's branch
    (lambda: chain()[2:], ["_Pool", "_vectorize"]),
])
def test_fold_engages_on_the_three_functions_in_order_and_a_sum_pool(
        nodes, folded):
    nodes = nodes()
    fns, arrays = api.fold_rowwise(*parts_of(nodes))
    names = [getattr(f, "__name__", type(f).__name__) for f in fns]
    assert names == folded
    assert len(arrays) == len(fns)
    run = RowwiseRun(nodes)
    assert run.folded == (len(folded) < len(nodes))
    # the plan and the programs see the same functions
    assert run._parts()[0] == fns
    x = images(3)
    want = Dataset.from_array(x)
    for node in nodes:
        want = node.apply_batch(want)
    np.testing.assert_allclose(
        np.asarray(run.apply_batch(Dataset.from_array(x)).array()),
        np.asarray(want.array()), rtol=1e-5)


def test_folded_run_that_fits_whole_still_runs_as_the_run(monkeypatch):
    """12 rows fit whole; node by node they would go through each node's
    own apply_batch and write every map. No node is asked, the chunk
    program runs once, and nothing in it is the size of a map."""
    nodes = chain()
    run = RowwiseRun(nodes)
    x = images(12)
    want = np.asarray(run._node_by_node(Dataset.from_array(x)).array())
    for node in nodes:
        monkeypatch.setattr(
            type(node), "apply_batch",
            lambda self, ds: pytest.fail("a node's own apply_batch ran"))
    whole = run.plan(x, None)
    assert not whole.chunked and whole.chunk_rows == 12
    before = counters()
    got = run.apply_batch(Dataset.from_array(x))
    np.testing.assert_allclose(np.asarray(got.array()), want, rtol=1e-5)
    made = {k: v - before.get(k, 0) for k, v in counters().items()}
    assert made["keystone_workflow_run_chunks_total"] == 1
    assert made["keystone_workflow_run_folded_items_total"] == 12
    # inside jit the folded functions run too (no node by node)
    jitted = jax.jit(
        lambda a: run.apply_batch(Dataset.from_array(a, n=10)).padded())
    inside = np.asarray(jitted(x))
    np.testing.assert_allclose(inside[:10], want[:10], rtol=1e-5)
    assert not inside[10:].any()


def test_folded_functions_write_no_map_at_the_published_widths():
    """Shapes alone (nothing is computed): of all the arrays the folded
    functions make for a chunk of 64 images at 10,000 filters the
    largest is the patches, 64 × 736 × 128; the three functions one
    after another make three maps of 64 × 27 × 27 × 10,000 and more."""
    from keystone_tpu.ops.images import core

    w = jax.ShapeDtypeStruct((10000, 6, 6, 3), jnp.float32)
    vec = jax.ShapeDtypeStruct((10000,), jnp.float32)
    fns = (core._Convolve(6, 3, True, 10.0, False), core._Rectify(0.0, 0.25),
           core._Pool(13, 14, None, None), core._vectorize)
    arrays = ((w, vec, vec), (), (), ())
    batch = jax.ShapeDtypeStruct((64, 32, 32, 3), jnp.float32)

    def largest(fns, arrays):
        jaxpr = jax.make_jaxpr(
            lambda a, b: api.run_rowwise.__wrapped__(fns, a, b))(arrays, batch)
        return max(
            v.aval.size for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars)

    a_map = 64 * 27 * 27 * 10000
    assert largest(fns, arrays) == 2 * a_map
    assert largest(*api.fold_rowwise(fns, arrays)) == 64 * 736 * 128
    assert out_of_run(fns, arrays, batch) == (64, 80000)
    assert out_of_run(*api.fold_rowwise(fns, arrays), batch) == (64, 80000)


def out_of_run(fns, arrays, batch):
    return jax.eval_shape(
        lambda a, b: api.run_rowwise.__wrapped__(fns, a, b), arrays, batch
    ).shape


def test_published_widths_plan_from_shapes_alone():
    """RandomPatchCifar as published: 12,544 rows at 10,000 filters on a
    16 GB chip. Shapes only (jax.eval_shape): nothing is computed."""
    w = jax.ShapeDtypeStruct((10000, 6, 6, 3), jnp.float32)
    vec = jax.ShapeDtypeStruct((10000,), jnp.float32)
    from keystone_tpu.ops.images import core

    fns = (core._Convolve(6, 3, True, 10.0, False), core._Rectify(0.0, 0.25),
           core._Pool(13, 14, None, None), core._vectorize)
    arrays = ((w, vec, vec), (), (), ())
    batch = jax.ShapeDtypeStruct((12544, 32, 32, 3), jnp.float32)
    free = int(15.75 * 2 ** 30) - 12544 * 32 * 32 * 3 * 4
    plan = plan_rowwise_run(fns, arrays, batch, free)
    maps, both = 27 * 27 * 10000 * 4, 27 * 27 * 20000 * 4
    assert plan.item_bytes == maps + both + 2 * 80000 * 4
    assert plan.out_bytes == 12544 * 80000 * 4
    assert plan.chunked and plan.chunk_rows == 64
    # a chunk's maps stay under half of what is free beside the result,
    # and no map is ever planned for all rows
    assert plan.chunk_bytes <= (free - plan.out_bytes) // 2
    assert plan.chunk_rows * both < 12544 * maps // 50
    # a little less free memory plans the same program
    assert plan_rowwise_run(fns, arrays, batch, free - 10 ** 9).chunk_rows == 64
    # scoring 2,048 held-out images beside a fit's model plans chunks too
    held = jax.ShapeDtypeStruct((2048, 32, 32, 3), jnp.float32)
    assert plan_rowwise_run(fns, arrays, held, free).chunk_rows == 64

    # the same run folded: a row holds its patches (736 × 128), the
    # kernel's sums, the pooled and the vectorised result and no map; the
    # plan stays a statement about bytes, so 12,544 rows do not fit whole
    folded, farrays = api.fold_rowwise(fns, arrays)
    assert len(folded) == 2 and farrays[0] == arrays[:3]
    plan = plan_rowwise_run(folded, farrays, batch, free)
    assert plan.item_bytes == 736 * 128 * 4 + 3 * 80000 * 4
    assert plan.item_bytes < maps // 20
    assert plan.out_bytes == 12544 * 80000 * 4
    # 4,096 rows fit; the rows are divided evenly over the 4 chunks that
    # asks for, and none is computed twice (4,096 a chunk would compute
    # 3,840 rows twice, a third of the featurizer again)
    assert plan.chunked and plan.chunk_rows == 3136
    assert 4 * plan.chunk_rows == 12544
    assert plan.chunk_bytes <= (free - plan.out_bytes) // 2
    assert plan_rowwise_run(
        folded, farrays, batch, free - 10 ** 9).chunk_rows == 3136
    # the 2,048 held-out images fit whole: one chunk of the run
    plan = plan_rowwise_run(folded, farrays, held, free)
    assert not plan.chunked and plan.chunk_rows == 2048


def test_rule_merges_the_run_on_the_normal_path(monkeypatch):
    nodes = chain()
    x = images(20)
    pipe = nodes[0].and_then(nodes[1]).and_then(nodes[2]).and_then(nodes[3])
    fitted = pipe.and_then(StandardScaler(), x).fit()
    labels = [op.label for op in fitted.graph.operators.values()]
    assert labels == [
        "Convolver+SymmetricRectifier+Pooler+ImageVectorizer",
        "StandardScalerModel",
    ]
    run = next(op for op in fitted.graph.operators.values()
               if isinstance(op, RowwiseRun))
    want = np.asarray(fitted(Dataset.from_array(x)).array())
    monkeypatch.setattr(
        api, "_device_free_bytes", lambda batch: free_for(run, x, 8))
    before = counters().get("keystone_workflow_run_chunks_total", 0)
    got = np.asarray(pipe.and_then(StandardScaler(), x)(x).get().array())
    np.testing.assert_array_equal(got, want)
    # the estimator's training input and the scored batch: 3 chunks each
    assert counters()["keystone_workflow_run_chunks_total"] - before == 6
    # one datum goes through the same node
    one = np.asarray(fitted(x[3]))
    np.testing.assert_allclose(one, want[3], rtol=1e-5, atol=1e-5)


def test_rule_leaves_a_node_with_two_readers_whole():
    """A map that something else reads too has to be whole."""
    from keystone_tpu.workflow.api import Pipeline

    nodes = chain()
    x = images(6)
    head = nodes[0].and_then(nodes[1])
    both = Pipeline.gather([
        head.and_then(nodes[2]).and_then(nodes[3]),
        head.and_then(Pooler(9, 10)).and_then(ImageVectorizer()),
    ])
    result = both(x)
    labels = sorted(op.label for op in result._executor.graph.operators.values())
    assert labels == [
        "Convolver+SymmetricRectifier", "Pooler+ImageVectorizer",
        "Pooler+ImageVectorizer", "dataset", "gather",
    ]
    a, b = result.get().array()
    assert a.shape == (6, 2 * 2 * 16) and b.shape[0] == 6


def test_rule_leaves_nodes_whose_function_is_for_shape_groups_alone():
    """The flagship's featurizers say how they map rows (so that ragged
    images can be noted on them) and say ``groups_only``: on one array
    each keeps its own ``apply_batch`` and the rule folds none of them,
    next to a row-wise run or alone."""
    from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
    from keystone_tpu.ops.stats import NormalizeRows, SignedHellingerMapper

    assert all(node.rowwise()[0].groups_only for node in (
        PixelScaler(), GrayScaler(), NormalizeRows(),
        SignedHellingerMapper()))
    nodes = chain()
    pipe = PixelScaler().and_then(nodes[0]).and_then(nodes[1]) \
        .and_then(nodes[2]).and_then(nodes[3]) \
        .and_then(NormalizeRows()).and_then(SignedHellingerMapper())
    result = pipe(images(6))
    labels = [op.label for op in result._executor.graph.operators.values()]
    assert sorted(labels) == sorted([
        "dataset", "PixelScaler",
        "Convolver+SymmetricRectifier+Pooler+ImageVectorizer",
        "NormalizeRows", "SignedHellingerMapper",
    ])
    assert result.get().array().shape[0] == 6


def test_equal_settings_share_one_program():
    """A fit builds its filters anew: the chunk program is keyed by the
    nodes' settings and not by their arrays."""
    x = images(16)
    runs = [RowwiseRun(chain(seed=s)) for s in (0, 1)]
    fns = [tuple(n.rowwise()[0] for n in r.nodes) for r in runs]
    assert fns[0] == fns[1] and hash(fns[0]) == hash(fns[1])
    # and so are the folded functions the programs are keyed by
    folded = [r._parts()[0] for r in runs]
    assert len(folded[0]) == 2
    assert folded[0] == folded[1] and hash(folded[0]) == hash(folded[1])
    a = runs[0].apply_batch(Dataset.from_array(x)).array()
    b = runs[1].apply_batch(Dataset.from_array(x)).array()
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_merged_run_keeps_its_nodes_weights_in_the_model_token():
    """serving/aot.py keys compiled programs and shared engines by the
    fitted pipeline's content: the filters inside a merged run count."""
    from keystone_tpu.serving.aot import pipeline_token

    def fitted(seed):
        nodes = chain(seed=seed)
        pipe = nodes[0].and_then(nodes[1]).and_then(nodes[2]).and_then(nodes[3])
        return pipe.and_then(StandardScaler(), images(8)).fit()

    assert pipeline_token(fitted(0)) == pipeline_token(fitted(0))
    assert pipeline_token(fitted(0)) != pipeline_token(fitted(1))
