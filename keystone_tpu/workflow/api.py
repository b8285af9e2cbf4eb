"""Typed user-facing pipeline API.

Reference semantics: workflow/{Transformer,Estimator,LabelEstimator,Chainable,
Pipeline,PipelineResult,PipelineDataset,PipelineDatum,FittedPipeline}.scala and
GatherTransformerOperator.scala, re-designed for JAX:

- ``Transformer.apply(x)`` is a pure function on arrays; the batch path
  defaults to ``vmap`` over the dataset's example axis when data is in array
  mode (one XLA program over the sharded batch) and a host map otherwise.
- ``Pipeline.fit()`` executes estimator fits (memoized by structural prefix
  across pipelines — the "do not fit estimators multiple times" guarantee)
  and returns a serializable ``FittedPipeline`` whose steady-state apply path
  can be staged into a single jit-compiled function (``FittedPipeline.jit``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import pickle
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel.chunks import (
    _run_chunk,
    account_of,
    chunk_starts,
    device_free_bytes as _device_free_bytes,
    leading_dim as _leading_dim,
    rows_a_chunk,
    tree_bytes as _tree_bytes,
    valid_rows as _valid_rows,
)
from keystone_tpu.parallel.dataset import (
    Dataset,
    count_chunked as _count_chunked,
)
from keystone_tpu.workflow.executor import GraphExecutor, PipelineEnv
from keystone_tpu.workflow.expressions import (
    DatasetExpression,
    DatumExpression,
)
from keystone_tpu.workflow.graph import (
    EMPTY_GRAPH,
    Graph,
    NodeId,
    SinkId,
    SourceId,
    linearize,
)
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    Operator,
    TransformerOperator,
)
from keystone_tpu.workflow.rules import UnusedBranchRemovalRule


# rows per jit(vmap) dispatch of a ``bucket_vmap`` node outside ``jit``.
# Dense SIFT at 256x256 keeps ~20 MB per image in flight, so one dispatch
# over a whole training set does not fit a 16 GB chip; 128 is the chunk
# the flagship settled on (two to a 256-image step of `flagship-score`,
# PERF.md). What the node is handed decides the
# rest: items of one shape and dtype, or an array, go through as slices of
# one array and come back in array mode (``_chunked_batch``); items of two
# or more shapes become one array a shape (``Dataset.grouped``), on which
# the node is noted and runs when its rows are asked for, a chunk sized
# from bytes at a time; a tracer, or an array of at most this many rows,
# is one ``vmap`` call.
BUCKET_CHUNK = 128


def _more_than_a_chunk(x: Any) -> bool:
    """A concrete array of more than BUCKET_CHUNK rows. A tracer is not
    one: inside ``jit`` the compiler schedules the memory, and a staged
    program keeps its single ``vmap``."""
    return (
        isinstance(x, (jax.Array, np.ndarray))
        and not isinstance(x, jax.core.Tracer)
        and x.shape[0] > BUCKET_CHUNK
    )


def _zero_padded(batch: Any, rows: int) -> Any:
    """``batch`` with zero rows appended up to ``rows``."""
    short = rows - batch.shape[0]
    if not short:
        return batch
    pad = jnp.zeros((short,) + batch.shape[1:], batch.dtype)
    return jnp.concatenate([batch, pad])


def _array_digest(a: np.ndarray) -> Any:
    """Fixed-size fingerprint of an array's contents. CSE/prefix keys hold
    this digest, never the raw bytes, so key size (and key comparison cost)
    doesn't scale with parameter bytes."""
    h = hashlib.blake2b(digest_size=16)
    h.update(str(a.shape).encode())
    h.update(str(a.dtype).encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return ("arr", a.shape, str(a.dtype), h.hexdigest())


def _hashable(v: Any) -> Any:
    if isinstance(v, np.ndarray):
        return _array_digest(v)
    if isinstance(v, jax.Array):
        return _array_digest(np.asarray(v))
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    try:
        hash(v)
        return v
    except TypeError:
        return id(v)


def _cached_hashable(self, v: Any) -> Any:
    """_hashable with the expensive array-digest step memoized per
    (instance, array identity) — but ONLY for immutable arrays
    (jax.Array, or np.ndarray with writeable=False): identity is a sound
    cache key only when the bytes can't change underneath it. Mutable
    np.ndarrays and cheap scalar fields are digested fresh each call, so
    in-place mutation still produces a fresh key."""
    immutable = isinstance(v, jax.Array) or (
        isinstance(v, np.ndarray) and not v.flags.writeable
    )
    if immutable:
        cache = self.__dict__.setdefault("_arr_digest_cache", {})
        hit = cache.get(id(v))
        if hit is None:
            hit = _hashable(v)
            cache[id(v)] = hit
            # hold a reference so id() can't be recycled
            cache[(id(v), "ref")] = v
        return hit
    if isinstance(v, (np.ndarray, jax.Array)):
        return _hashable(v)
    if isinstance(v, (list, tuple)):
        return tuple(_cached_hashable(self, x) for x in v)
    if isinstance(v, dict):
        return tuple(
            sorted((k, _cached_hashable(self, x)) for k, x in v.items())
        )
    return _hashable(v)


def _dataclass_eq_key(self) -> Any:
    """Structural key for dataclass operators. The device->host transfer +
    serialization of array fields happens at most once per distinct array
    per operator no matter how often the optimizer recomputes prefixes/CSE
    signatures (the reference relies on case-class equality, Scala-side
    cheap; EquivalentNodeMergeRule.scala:13-15)."""
    if not dataclasses.is_dataclass(self):
        return id(self)
    return (
        type(self),
        tuple(
            (f.name, _cached_hashable(self, getattr(self, f.name)))
            for f in dataclasses.fields(self)
        ),
    )


class Chainable:
    """Anything composable into a pipeline via ``and_then``."""

    def to_pipeline(self) -> "Pipeline":
        raise NotImplementedError

    def and_then(
        self,
        nxt: Union["Chainable", "Estimator", "LabelEstimator"],
        data: Any = None,
        labels: Any = None,
    ) -> "Pipeline":
        pipe = self.to_pipeline()
        if isinstance(nxt, LabelEstimator):
            if data is None or labels is None:
                raise TypeError("LabelEstimator chaining needs data and labels")
            return pipe._concat(nxt.with_data(pipe(data), labels))
        if isinstance(nxt, Estimator):
            if data is None:
                raise TypeError("Estimator chaining needs data")
            return pipe._concat(nxt.with_data(pipe(data)))
        return pipe._concat(nxt.to_pipeline())

    def __call__(self, data: Any) -> "PipelineResult":
        return self.to_pipeline().apply(data)

    def apply(self, data: Any) -> "PipelineResult":
        return self.to_pipeline().apply(data)


class Pipeline(Chainable):
    """A (GraphExecutor, source, sink) triple — one dangling input, one
    output. Applying data splices it in place of the source; execution stays
    lazy until ``PipelineResult.get()``."""

    def __init__(self, executor: GraphExecutor, source: SourceId, sink: SinkId):
        self.executor = executor
        self.source = source
        self.sink = sink

    # -- construction ------------------------------------------------------

    @property
    def _graph(self) -> Graph:
        return self.executor.raw_graph

    def to_pipeline(self) -> "Pipeline":
        return self

    def _concat(self, nxt: "Pipeline") -> "Pipeline":
        g, _, sink_map = self._graph.connect_graph(
            nxt._graph, {nxt.source: self.sink}
        )
        return Pipeline(GraphExecutor(g), self.source, sink_map[nxt.sink])

    # -- application -------------------------------------------------------

    def apply(self, data: Any) -> "PipelineResult":
        if isinstance(data, PipelineDataset):
            g, _, sink_map = data._graph.connect_graph(
                self._graph, {self.source: data._sink}
            )
            return PipelineDataset(GraphExecutor(g), sink_map[self.sink])
        if isinstance(data, PipelineDatum):
            g, _, sink_map = data._graph.connect_graph(
                self._graph, {self.source: data._sink}
            )
            return PipelineDatum(GraphExecutor(g), sink_map[self.sink])
        if isinstance(data, Dataset) or isinstance(data, (list,)) or (
            hasattr(data, "ndim") and data.ndim >= 2
        ):
            return self.apply(PipelineDataset.of(Dataset.of(data)))
        return self.apply_datum(data)

    def apply_datum(self, datum: Any) -> "PipelineDatum":
        g, nid = self._graph.add_node(DatumOperator(datum), ())
        g = g.replace_dependency(self.source, nid)
        g = g.remove_source(self.source)
        return PipelineDatum(GraphExecutor(g), self.sink)

    # -- training ----------------------------------------------------------

    def fit(self) -> "FittedPipeline":
        """Execute every estimator fit (prefix-memoized), swap delegating
        nodes for the fit transformers, prune, freeze."""
        executor = self.executor
        g = executor.graph  # optimized
        for n in sorted(g.operators.keys()):
            if isinstance(g.operators[n], DelegatingOperator):
                deps = g.dependencies[n]
                est_dep = deps[0]
                fit_transformer = executor.execute(est_dep).get()
                if not isinstance(fit_transformer, TransformerOperator):
                    raise TypeError(
                        f"estimator fit returned {type(fit_transformer)}"
                    )
                g = g.set_operator(n, fit_transformer)
                g = g.set_dependencies(n, deps[1:])
        # keep only the apply path from source to sink
        g_pruned, _ = UnusedBranchRemovalRule().apply(
            Graph(
                sources=g.sources,
                sink_dependencies={self.sink: g.sink_dependencies[self.sink]},
                operators=g.operators,
                dependencies=g.dependencies,
            ),
            {},
        )
        for n, op in g_pruned.operators.items():
            if not isinstance(op, TransformerOperator):
                raise TypeError(
                    f"fit pipeline contains non-transformer node {n}: {op!r}"
                )
        return FittedPipeline(g_pruned, self.source, self.sink)

    # -- combinators -------------------------------------------------------

    @staticmethod
    def gather(branches: Sequence[Chainable]) -> "Pipeline":
        """Merge N single-input branches onto one shared source; output per
        example is the tuple of branch outputs (reference: Pipeline.gather +
        GatherTransformerOperator)."""
        g, src = EMPTY_GRAPH.add_source()
        ends: List = []
        for branch in branches:
            bp = branch.to_pipeline()
            g, smap, kmap = g.add_graph(bp._graph)
            g = g.replace_dependency(smap[bp.source], src)
            g = g.remove_source(smap[bp.source])
            end = g.sink_dependencies[kmap[bp.sink]]
            g = g.remove_sink(kmap[bp.sink])
            ends.append(end)
        g, gather_node = g.add_node(GatherTransformerOperator(), ends)
        g, sink = g.add_sink(gather_node)
        return Pipeline(GraphExecutor(g), src, sink)

    def to_dot(self) -> str:
        return self._graph.to_dot()


class PipelineResult:
    """Lazily executed sink value."""

    def __init__(self, executor: GraphExecutor, sink: SinkId):
        self._executor = executor
        self._sink = sink
        self._result: Any = None
        self._done = False

    @property
    def _graph(self) -> Graph:
        return self._executor.raw_graph

    def get(self) -> Any:
        if not self._done:
            self._result = self._executor.execute(self._sink).get()
            self._done = True
        return self._result


class PipelineDataset(PipelineResult):
    def get(self) -> Dataset:
        return super().get()

    @staticmethod
    def of(dataset: Dataset) -> "PipelineDataset":
        g, nid = EMPTY_GRAPH.add_node(DatasetOperator(dataset), ())
        g, sink = g.add_sink(nid)
        return PipelineDataset(GraphExecutor(g), sink)


class PipelineDatum(PipelineResult):
    @staticmethod
    def of(datum: Any) -> "PipelineDatum":
        g, nid = EMPTY_GRAPH.add_node(DatumOperator(datum), ())
        g, sink = g.add_sink(nid)
        return PipelineDatum(GraphExecutor(g), sink)


class Transformer(Chainable, TransformerOperator):
    """A pure per-example function, liftable to a one-node pipeline.

    Subclasses override ``apply(x)``; override ``apply_batch(ds)`` for a
    hand-batched path (most array ops should — one matmul beats vmap of
    per-row ops only when XLA can't fuse, but explicit batch code also skips
    per-item host dispatch for items-mode data). ``vmap_batch=False`` forces
    host-side per-item mapping (non-traceable transformers).
    """

    vmap_batch: bool = True
    # shape-bucketed vmap for ragged items-mode data: group items by
    # shape, one jit(vmap) dispatch per group instead of one host-mapped
    # dispatch chain per image.
    bucket_vmap: bool = False

    def apply(self, x: Any) -> Any:  # single datum
        raise NotImplementedError

    def rowwise(self) -> Optional[tuple]:
        """``(fn, arrays)`` where ``fn(arrays, batch)`` is this node's
        array-mode ``apply_batch`` as a traceable function that maps row
        i of ``batch`` to row i of its result and looks at no other row;
        None (the default) for a node that says no such thing. ``fn``
        hashes and compares by its settings, the node's arrays go in
        ``arrays``: nodes of equal settings then share compiled programs
        (``RowwiseRun``). Two things ``fn`` may say besides
        (``fold_rowwise``, ``plan_rowwise_run``): ``fn.absorb(rest)``,
        given the functions that follow it in a run, returns ``(folded,
        k)`` where one function does its work and that of the next
        ``k``, or None; ``fn.held(arrays, batch)`` returns the arrays a
        row keeps on the device beside its result. A node that gives a
        function is also noted on ragged data (``Dataset.then``) and
        runs, with the nodes noted before and after it, when the rows
        are asked for; ``fn.groups_only`` (true) says that the function
        is for that alone: on one array the node keeps its own
        ``apply_batch`` and ``RowwiseRunRule`` leaves it out of runs
        (the flagship's featurizers, whose programs on uniform batches
        are as they were before shape groups)."""
        return None

    def _jitted_vmap(self):
        fn = self.__dict__.get("_vmapped_apply")
        if fn is None:
            fn = jax.jit(jax.vmap(self.apply))
            self.__dict__["_vmapped_apply"] = fn
        return fn

    def apply_batch(self, ds: Dataset) -> Dataset:
        if ds.is_array and (self.vmap_batch or self.bucket_vmap):
            x = ds.padded()
            if self.bucket_vmap and _more_than_a_chunk(x):
                return self._chunked_batch(x, ds.n)
            return Dataset.from_array(self._jitted_vmap()(x), n=ds.n)
        if self.bucket_vmap:
            return self._bucketed_batch(ds)
        return ds.map(self.apply)

    def _bucketed_batch(self, ds: Dataset) -> Dataset:
        """Items through ``jit(vmap(apply))`` in chunks. Items of one shape
        and dtype become one array, go through ``_chunked_batch`` and come
        back in array mode; ragged items become one array a shape
        (``Dataset.grouped``: a put a group) on which this node is noted,
        to run when its rows are asked for. Spans: ``workflow.upload``
        once, ``workflow.stack`` / ``.apply`` / ``.slice`` per chunk —
        never one per item."""
        with span("workflow.upload", n=ds.n):
            batch = None if ds.is_grouped else ds.uniform_array()
            groups = ds.grouped() if batch is None else None
        if batch is not None:
            return self._chunked_batch(batch, ds.n)
        if groups is None:  # items that are no arrays
            return ds.map(self.apply)
        return groups.then(*(self.rowwise() or (_VmapRows(self), ())))

    def _chunked_batch(self, x: Any, n: int) -> Dataset:
        """The rows of one array through ``jit(vmap(apply))``, a chunk of
        at most BUCKET_CHUNK rows a dispatch (the tail zero-padded to the
        chunk's shape, so all chunks share one program), the outputs
        joined into an array-mode result of the same rows, ``n`` of them
        valid. Spans per chunk: ``workflow.stack`` takes the chunk and pads
        it, ``.apply`` dispatches it, ``.slice`` drops the pad rows a
        featurizer made of the zeros (it does not map them to zeros) and,
        in the last chunk, joins the outputs."""
        rows = x.shape[0]
        chunk = min(rows, BUCKET_CHUNK)
        fn = self._jitted_vmap()
        outs: List[Any] = []
        for s in range(0, rows, chunk):
            valid = min(chunk, rows - s)
            with span("workflow.stack", n=valid):
                part = x if valid == rows else x[s : s + valid]
                part = _zero_padded(part, chunk)
            with span("workflow.apply", n=chunk):
                res = fn(part)
            with span("workflow.slice", n=valid):
                if valid < chunk:
                    res = jax.tree_util.tree_map(lambda a: a[:valid], res)
                outs.append(res)
                if s + chunk >= rows:
                    joined = res if len(outs) == 1 else jax.tree_util.tree_map(
                        lambda *parts: jnp.concatenate(parts), *outs
                    )
        _count_chunked(n, len(outs), len(outs) * chunk - rows, array_items=n)
        return Dataset.from_array(joined, n=n)

    # TransformerOperator ABI
    def single_transform(self, inputs: Sequence[Any]) -> Any:
        return self.apply(inputs[0])

    def batch_transform(self, inputs: Sequence[Dataset]) -> Dataset:
        ds = inputs[0]
        if ds.is_grouped:
            step = self.rowwise()
            if step is not None:
                return ds.then(*step)
        return self.apply_batch(ds)

    def to_pipeline(self) -> Pipeline:
        g, src = EMPTY_GRAPH.add_source()
        g, nid = g.add_node(self, (src,))
        g, sink = g.add_sink(nid)
        return Pipeline(GraphExecutor(g), src, sink)

    def __call__(self, data: Any) -> Any:
        return self.to_pipeline().apply(data)

    def eq_key(self) -> Any:
        return _dataclass_eq_key(self)

    @property
    def label(self) -> str:  # type: ignore[override]
        return type(self).__name__


@dataclasses.dataclass(frozen=True)
class _VmapRows:
    """``jit(vmap(node.apply))`` as a rows function for a node that gives
    none of its own; it compares by the node, so two nodes of equal
    settings compile twice."""

    node: Any

    def __call__(self, arrays, x):
        del arrays
        return jax.vmap(self.node.apply)(x)


@dataclasses.dataclass(frozen=True)
class RunPlan:
    """How ``RowwiseRun`` takes one batch through: ``chunk_rows`` rows a
    program (all of ``rows`` where the batch goes through whole),
    ``item_bytes`` what one row holds across the run (every function's
    output, and what a function says it holds beside it), ``out_item``
    the shapes of one row of the run's result."""

    rows: int
    chunk_rows: int
    item_bytes: int
    out_item: Any

    @property
    def chunked(self) -> bool:
        return self.chunk_rows < self.rows

    @property
    def chunk_bytes(self) -> int:
        return self.chunk_rows * self.item_bytes

    @property
    def out_bytes(self) -> int:
        return self.rows * _tree_bytes(self.out_item)


def plan_rowwise_run(
    fns: Sequence[Callable], arrays: Sequence[Any], batch: Any,
    free_bytes: Optional[int],
) -> RunPlan:
    """Rows a chunk from bytes, by ``jax.eval_shape`` alone: one row's
    outputs of every function of the run, and what a function says it
    holds beside its output (``fn.held``), against half of what the
    device has free once the joined result is taken out (the other half
    is the compiler's: a program's temporaries are not in the shapes).
    The rows that fit are cut to a power of two, so that a little more
    or less free memory plans the same program, and the batch is
    divided evenly over the chunks that many rows ask for: the last
    chunk starts ``chunk_rows`` before the end, and the rows it computes
    twice are fewer than there are chunks. The batch goes through whole
    where it fits, or where the backend gives no account of its memory."""
    rows = _leading_dim(batch)
    item_bytes, one = account_of(fns, arrays, batch)
    whole = RunPlan(rows, rows, item_bytes, one)
    return dataclasses.replace(whole, chunk_rows=rows_a_chunk(
        rows, item_bytes, whole.out_bytes, free_bytes))


def fold_rowwise(fns: Sequence[Callable], arrays: Sequence[Any]) -> tuple:
    """The functions of a run with every function that absorbs its
    successors (``fn.absorb``, see ``Transformer.rowwise``) in their
    place; the folded function's arrays are the tuple of the arrays of
    the functions it stands for."""
    out_fns, out_arrays, i = [], [], 0
    while i < len(fns):
        absorb = getattr(fns[i], "absorb", None)
        fn, k = (absorb and absorb(fns[i + 1:])) or (fns[i], 0)
        out_fns.append(fn)
        out_arrays.append(tuple(arrays[i:i + 1 + k]) if k else arrays[i])
        i += 1 + k
    return tuple(out_fns), tuple(out_arrays)


@partial(jax.jit, static_argnums=(0,))
def run_rowwise(fns, arrays, batch):
    """``rowwise()`` functions, one after another, on one whole batch:
    one program per tuple of functions (not per node) and batch shape."""
    for fn, arr in zip(fns, arrays):
        batch = fn(arr, batch)
    return batch


class RowwiseRun(Transformer):
    """Consecutive row-wise nodes as one node (``RowwiseRunRule`` makes
    it of nodes whose ``rowwise()`` says they map row to row). An
    array-mode batch whose intermediates fit the device goes from node to
    node as before. One that does not goes through the whole run a chunk
    of rows at a time, one program a chunk, and only the run's last
    output is ever whole: a Convolver's maps, megabytes an image, live
    for a chunk's rows and no longer.

    The run's functions are folded once (``fold_rowwise``), for the plan
    and for the programs alike. A run in which a function absorbed its
    successors (``folded``) holds less a row than its nodes would one
    by one, so it never goes node by node: a batch the plan calls whole
    is one chunk of the same program.

    The chunk's rows follow from bytes (``plan_rowwise_run``). All chunks
    share one program: the last one starts ``chunk_rows`` before the end
    and computes a few rows twice rather than pad. Spans
    ``workflow.run`` once a call and ``workflow.run.chunk`` once a
    chunk; counters ``keystone_workflow_run_items_total``,
    ``_run_folded_items_total`` (those of them in a folded run),
    ``_run_chunks_total`` and ``_run_chunk_bytes_total`` (the planned
    bytes of the chunks dispatched)."""

    def __init__(self, nodes: Sequence[Transformer]):
        self.nodes = tuple(nodes)

    @property
    def label(self) -> str:  # type: ignore[override]
        return "+".join(n.label for n in self.nodes)

    def eq_key(self) -> Any:
        return ("rowwise_run", tuple(n.eq_key() for n in self.nodes))

    def apply(self, x: Any) -> Any:
        for node in self.nodes:
            x = node.apply(x)
        return x

    def _node_by_node(self, ds: Dataset) -> Dataset:
        for node in self.nodes:
            ds = node.batch_transform([ds])
        return ds

    def _parts(self) -> tuple:
        """(the run's functions, their arrays), folded."""
        return fold_rowwise(*zip(*(node.rowwise() for node in self.nodes)))

    @property
    def folded(self) -> bool:
        """Some function of the run stands for several nodes."""
        return len(self._parts()[0]) < len(self.nodes)

    def plan(self, batch: Any, free_bytes: Optional[int]) -> RunPlan:
        return plan_rowwise_run(*self._parts(), batch, free_bytes)

    def apply_batch(self, ds: Dataset) -> Dataset:
        if not ds.is_array:
            return self._node_by_node(ds)
        batch = ds.padded()
        fns, arrays = self._parts()
        folded = len(fns) < len(self.nodes)
        if any(
            isinstance(a, jax.core.Tracer)
            for a in jax.tree_util.tree_leaves(batch)
        ):  # inside jit the compiler schedules the memory
            if not folded:
                return self._node_by_node(ds)
            for fn, arr in zip(fns, arrays):
                batch = fn(arr, batch)
            return Dataset.from_array(_valid_rows(batch, 0, ds.n), n=ds.n)
        plan = plan_rowwise_run(
            fns, arrays, batch, _device_free_bytes(batch)
        )
        if not plan.chunked and not folded:
            return self._node_by_node(ds)
        return self._chunked(ds, batch, plan, fns, arrays)

    def _chunked(
        self, ds: Dataset, batch: Any, plan: RunPlan, fns: tuple,
        arrays: tuple,
    ) -> Dataset:
        rows, chunk = plan.rows, plan.chunk_rows
        starts = chunk_starts(rows, chunk)
        with span("workflow.run", n=ds.n, chunks=len(starts),
                  chunk_rows=chunk, chunk_bytes=plan.chunk_bytes):
            out = jax.tree_util.tree_map(
                lambda a: jnp.zeros((rows,) + a.shape[1:], a.dtype),
                plan.out_item,
            )
            for start in starts:
                with span("workflow.run.chunk", n=chunk):
                    out = _run_chunk(
                        fns, chunk, arrays, out, batch, start, ds.n
                    )
        reg = get_global_registry()
        reg.counter(
            "keystone_workflow_run_items_total",
            "items that went through a RowwiseRun in chunks",
        ).inc(by=ds.n)
        reg.counter(
            "keystone_workflow_run_folded_items_total",
            "items that went through a RowwiseRun in which a function "
            "absorbed its successors",
        ).inc(by=ds.n if len(fns) < len(self.nodes) else 0)
        reg.counter(
            "keystone_workflow_run_chunks_total",
            "chunk programs RowwiseRun dispatched",
        ).inc(by=len(starts))
        reg.counter(
            "keystone_workflow_run_chunk_bytes_total",
            "bytes RowwiseRun planned for the chunks it dispatched",
        ).inc(by=len(starts) * plan.chunk_bytes)
        return Dataset.from_array(out, n=ds.n)


def transformer(fn: Callable[[Any], Any], name: str = None) -> Transformer:
    """Factory: lift a plain function into a Transformer
    (reference: Transformer.apply(f))."""

    class _FnTransformer(Transformer):
        def apply(self, x):
            return fn(x)

        def eq_key(self):
            return ("fn", fn)

    t = _FnTransformer()
    t.__class__.__name__ = name or getattr(fn, "__name__", "fn")
    return t


class Estimator(Chainable, EstimatorOperator):
    """fit(Dataset) -> Transformer; splice-able into a pipeline."""

    def fit(self, data: Dataset) -> Transformer:
        raise NotImplementedError

    def fit_datasets(self, datasets: Sequence[Dataset]) -> TransformerOperator:
        return self.fit(datasets[0])

    def with_data(self, data: Any) -> Pipeline:
        g, data_end = _splice_data(EMPTY_GRAPH, data)
        g, est_node = g.add_node(self, (data_end,))
        g, src = g.add_source()
        g, delegate = g.add_node(DelegatingOperator(), (est_node, src))
        g, sink = g.add_sink(delegate)
        return Pipeline(GraphExecutor(g), src, sink)

    def to_pipeline(self) -> Pipeline:
        raise TypeError(
            "an Estimator is not directly chainable; use and_then(est, data)"
        )

    def eq_key(self) -> Any:
        return _dataclass_eq_key(self)

    @property
    def label(self) -> str:  # type: ignore[override]
        return type(self).__name__


class LabelEstimator(Estimator):
    """fit(Dataset, labels: Dataset) -> Transformer."""

    def fit(self, data: Dataset, labels: Dataset) -> Transformer:  # type: ignore[override]
        raise NotImplementedError

    def fit_datasets(self, datasets: Sequence[Dataset]) -> TransformerOperator:
        return self.fit(datasets[0], datasets[1])

    def with_data(self, data: Any, labels: Any = None) -> Pipeline:
        if labels is None:
            raise TypeError("LabelEstimator.with_data needs labels")
        g, data_end = _splice_data(EMPTY_GRAPH, data)
        g, labels_end = _splice_data(g, labels)
        g, est_node = g.add_node(self, (data_end, labels_end))
        g, src = g.add_source()
        g, delegate = g.add_node(DelegatingOperator(), (est_node, src))
        g, sink = g.add_sink(delegate)
        return Pipeline(GraphExecutor(g), src, sink)


def _splice_data(g: Graph, data: Any):
    """Attach a data producer to ``g``: a constant dataset node, or the whole
    upstream graph of a PipelineDataset (so shared prefixes stay shared)."""
    if isinstance(data, PipelineResult):
        if data._graph.sources:
            raise ValueError("cannot splice a pipeline with dangling sources")
        g2, _, kmap = g.add_graph(data._graph)
        end = g2.sink_dependencies[kmap[data._sink]]
        g2 = g2.remove_sink(kmap[data._sink])
        return g2, end
    ds = Dataset.of(data)
    return g.add_node(DatasetOperator(ds), ())


class FunctionNode:
    """Eagerly-applied pipeline-construction-time function (reference:
    pipelines/FunctionNode.scala) — not a DAG node."""

    def __call__(self, data: Any) -> Any:
        return self.apply(data)

    def apply(self, data: Any) -> Any:
        raise NotImplementedError


class GatherTransformerOperator(TransformerOperator):
    """Zips N branch outputs into a per-example tuple."""

    label = "gather"

    def single_transform(self, inputs: Sequence[Any]) -> Any:
        return tuple(inputs)

    def batch_transform(self, inputs: Sequence[Dataset]) -> Dataset:
        n = inputs[0].n
        if any(ds.n != n for ds in inputs):
            raise ValueError("gather branches disagree on dataset length")
        if all(ds.is_array for ds in inputs):
            pn = max(ds.padded_n for ds in inputs)
            arrs = tuple(ds._pad_to(pn).padded() for ds in inputs)
            return Dataset.from_array(arrs, n=n)
        cols = [ds.items() for ds in inputs]
        return Dataset.from_items([tuple(row) for row in zip(*cols)])

    def eq_key(self) -> Any:
        return ("gather",)


class Identity(Transformer):
    def apply(self, x):
        return x

    def apply_batch(self, ds: Dataset) -> Dataset:
        return ds

    def eq_key(self):
        return ("identity",)


class FittedPipeline:
    """A train-free, serializable transformer-only pipeline.

    ``apply`` interprets the graph node-by-node (cheap — the work is inside
    batched XLA ops); ``jit()`` stages the whole single-example path into one
    compiled XLA program for steady-state serving.
    """

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self.graph = graph
        self.source = source
        self.sink = sink
        self._topo = [
            gid for gid in linearize(graph) if isinstance(gid, NodeId)
        ]

    def _run(self, feed: Any, batch: bool) -> Any:
        values: Dict[Any, Any] = {self.source: feed}
        for n in self._topo:
            op = self.graph.operators[n]
            ins = [values[d] for d in self.graph.dependencies[n]]
            if batch:
                values[n] = op.batch_transform(ins)
            else:
                values[n] = op.single_transform(ins)
        return values[self.graph.sink_dependencies[self.sink]]

    def apply(self, data: Any) -> Any:
        if isinstance(data, PipelineResult):
            data = data.get()
        if isinstance(data, Dataset):
            return self._run(data, batch=True)
        return self._run(data, batch=False)

    __call__ = apply

    def jit(self) -> Callable[[Any], Any]:
        """The single-example apply path as one jitted function."""
        return jax.jit(lambda x: self._run(x, batch=False))

    def _batch_run(self, arr: Any) -> Any:
        """The traceable whole-batch apply path: array(s) in, array(s)
        out. Shared staging surface of ``jit_batch`` and the serving
        engine (serving/engine.py), so the two can't drift. Rows past
        the valid count are zeros by the Dataset pad discipline; callers
        slice outputs back to their valid rows."""
        out = self._run(Dataset.from_array(arr), batch=True)
        return out.padded() if isinstance(out, Dataset) else out

    def jit_batch(self, donate: bool = False) -> Callable[[Any], Any]:
        """The WHOLE batched apply path as ONE compiled XLA program —
        the SURVEY §7 lowering: array in, array out, every node's
        batch_transform traced into a single staged computation (XLA
        fuses across node boundaries; no per-node dispatch). Requires an
        array-mode transformer chain (host-side items-mode nodes, e.g.
        string tokenizers, cannot trace — use ``apply`` for those).

        NOTE: one program per distinct batch shape — every new batch
        size recompiles. For serving arbitrary request sizes use
        ``compiled()`` (bucketed execution, bounded compiles).

        ``donate=True`` donates the input buffer to XLA (halves peak
        HBM for the staged batch; the caller's array is consumed)."""
        return jax.jit(
            self._batch_run, donate_argnums=(0,) if donate else ()
        )

    def compiled(self, buckets=None, **kwargs):
        """This pipeline as a serving engine: bucketed compiled
        execution with bounded recompiles, input donation, and optional
        mesh sharding (see serving/engine.py ``CompiledPipeline``)."""
        from keystone_tpu.serving.engine import (
            DEFAULT_BUCKETS, CompiledPipeline,
        )

        return CompiledPipeline(
            self, buckets if buckets is not None else DEFAULT_BUCKETS,
            **kwargs,
        )

    def and_then(self, nxt: "FittedPipeline") -> "FittedPipeline":
        g, _, sink_map = self.graph.connect_graph(
            nxt.graph, {nxt.source: self.sink}
        )
        return FittedPipeline(g, self.source, sink_map[nxt.sink])

    # -- persistence (reference: FittedPipeline is Serializable) ----------

    def save(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump(self, f)

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        with open(path, "rb") as f:
            return pickle.load(f)
