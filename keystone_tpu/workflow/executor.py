"""Memoizing graph executor + process-global pipeline environment.

Reference semantics: workflow/GraphExecutor.scala (memoized recursive
interpretation, optimize-once-lazily, refuse to execute source-dependent ids,
save executed prefixes into the global state) and workflow/PipelineEnv.scala
(process singleton holding cross-pipeline prefix state and the optimizer).
"""

from __future__ import annotations

from typing import Dict, Optional, Set, Tuple

from keystone_tpu.observability.tracing import span
from keystone_tpu.workflow.expressions import Expression
from keystone_tpu.workflow.graph import (
    Graph,
    GraphId,
    NodeId,
    SinkId,
    SourceId,
    get_ancestors,
)
from keystone_tpu.workflow.operators import ExpressionOperator
from keystone_tpu.workflow.prefix import Prefix


class PipelineEnv:
    """Process-global: prefix-keyed saved state + the active optimizer."""

    _instance: Optional["PipelineEnv"] = None

    def __init__(self):
        self.state: Dict[Prefix, Expression] = {}
        self._optimizer = None

    @classmethod
    def get_or_create(cls) -> "PipelineEnv":
        if cls._instance is None:
            cls._instance = PipelineEnv()
        return cls._instance

    @property
    def optimizer(self):
        if self._optimizer is None:
            from keystone_tpu.workflow.optimizer import DefaultOptimizer

            self._optimizer = DefaultOptimizer()
        return self._optimizer

    @optimizer.setter
    def optimizer(self, opt) -> None:
        self._optimizer = opt

    def reset(self) -> None:
        self.state = {}
        self._optimizer = None

    # -- persistence (SURVEY §5 checkpoint level 2: the prefix state is a
    # content-addressed cache keyed by structural prefix hash; persisting
    # it lets re-built pipelines in a NEW process skip recompute) --------

    def save_state(
        self,
        path: str,
        *,
        large_array_bytes: int = 1 << 20,
        max_total_bytes: Optional[int] = None,
    ) -> None:
        """Persist every materialized prefix expression to a directory:
        ``index.pkl`` plus one ``.npy`` file per large array.

        Arrays over ``large_array_bytes`` stream to their own file one at
        a time (device -> host -> disk, then released) so a flagship-scale
        cached feature dataset never needs the whole state resident on
        host at once. ``max_total_bytes`` caps what gets written: an
        entry that would exceed the budget is skipped whole (its partial
        files are removed and un-charged), in state-iteration order.
        Unevaluated (never-forced) expressions are skipped, not forced.
        """
        import os
        import pickle

        import jax
        import numpy as np

        from keystone_tpu.parallel.dataset import Dataset

        os.makedirs(path, exist_ok=True)
        index = {}
        written = 0
        counter = 0

        def persist_tree(tree):
            """Replace large arrays with .npy file references; returns
            the persisted tree, or None (with files and budget rolled
            back) if the entry would exceed the budget."""
            nonlocal counter, written
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            out_leaves = []
            entry_files = []
            entry_bytes = 0

            def rollback():
                nonlocal written
                for f in entry_files:
                    try:
                        os.remove(os.path.join(path, f))
                    except OSError:
                        pass
                written -= entry_bytes

            for leaf in leaves:
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                    a = np.asarray(leaf)
                    if (
                        max_total_bytes is not None
                        and written + a.nbytes > max_total_bytes
                    ):
                        rollback()
                        return None
                    if a.nbytes >= large_array_bytes:
                        fname = f"arr{counter:05d}.npy"
                        counter += 1
                        np.save(os.path.join(path, fname), a)
                        written += a.nbytes
                        entry_bytes += a.nbytes
                        entry_files.append(fname)
                        out_leaves.append(("npy", fname))
                        del a
                        continue
                    written += a.nbytes
                    entry_bytes += a.nbytes
                    out_leaves.append(("arr", a))
                else:
                    out_leaves.append(("raw", leaf))
            return jax.tree_util.tree_unflatten(treedef, out_leaves)

        for prefix, expr in self.state.items():
            if not expr.is_computed:
                continue
            value = expr.get()
            if isinstance(value, Dataset):
                if value.is_array:
                    tree = persist_tree(value.padded())
                    if tree is None:
                        continue
                    entry = ("dataset_array", tree, value.n)
                else:
                    tree = persist_tree(value.items())
                    if tree is None:
                        continue
                    entry = ("dataset_items", tree, None)
            else:
                entry = ("raw", value, None)
            try:
                pickle.dumps(entry)
            except Exception:
                continue  # unpicklable (e.g. closure-defined transformer)
            index[prefix] = entry
        with open(os.path.join(path, "index.pkl"), "wb") as f:
            pickle.dump(index, f)

    def load_state(self, path: str) -> int:
        """Load persisted prefix state; returns the number of entries."""
        import os
        import pickle

        import jax
        import numpy as np

        from keystone_tpu.parallel.dataset import Dataset
        from keystone_tpu.workflow.expressions import (
            DatasetExpression,
            DatumExpression,
        )

        with open(os.path.join(path, "index.pkl"), "rb") as f:
            saved = pickle.load(f)

        def restore_tree(tree):
            def restore(leaf):
                kind, payload = leaf
                if kind == "npy":
                    return np.load(os.path.join(path, payload))
                return payload

            return jax.tree_util.tree_map(
                restore, tree,
                is_leaf=lambda x: isinstance(x, tuple)
                and len(x) == 2
                and isinstance(x[0], str)
                and x[0] in ("npy", "arr", "raw"),
            )

        for prefix, (kind, payload, n) in saved.items():
            if kind == "dataset_array":
                ds = Dataset.from_array(restore_tree(payload), n=n)
                self.state[prefix] = DatasetExpression.of(ds)
            elif kind == "dataset_items":
                ds = Dataset.from_items(restore_tree(payload))
                self.state[prefix] = DatasetExpression.of(ds)
            else:
                self.state[prefix] = DatumExpression.of(payload)
        return len(saved)


class GraphExecutor:
    """Executes a graph, memoizing per-id expressions.

    ``optimize=True`` runs the environment's optimizer once, lazily, before
    the first execution. Ids with a source ancestor cannot be executed (their
    value depends on unspliced runtime data).

    Observability: every node's own work runs inside a
    ``node:<label>`` span (``observability.tracing.span``: ``ks:node:
    <label>`` in a profiler trace, and in ``/tracez`` once
    ``enable_tracing()`` is on). A node's value is a lazy expression, so
    the span opens when the value is first asked for, after the node's
    dependencies have been forced: node spans follow one another and do
    not nest, a span's duration is the node's own time, and the phases
    inside it (``workflow.*``, ``solver.*``) are its children.
    ``workflow.optimize`` covers the optimizer's one run.
    """

    def __init__(
        self,
        graph: Graph,
        optimize: bool = True,
    ):
        self._raw_graph = graph
        self._optimize = optimize
        self._optimized: Optional[Tuple[Graph, Dict[NodeId, Prefix]]] = None
        self._execution_state: Dict[GraphId, Expression] = {}
        self._source_dependants: Optional[Set[GraphId]] = None

    @property
    def raw_graph(self) -> Graph:
        return self._raw_graph

    @property
    def graph(self) -> Graph:
        return self._optimized_graph_and_prefixes()[0]

    @property
    def prefixes(self) -> Dict[NodeId, Prefix]:
        return self._optimized_graph_and_prefixes()[1]

    def _optimized_graph_and_prefixes(self):
        if self._optimized is None:
            if self._optimize:
                env = PipelineEnv.get_or_create()
                with span("workflow.optimize"):
                    self._optimized = env.optimizer.execute(self._raw_graph)
            else:
                self._optimized = (self._raw_graph, {})
        return self._optimized

    def _unexecutable(self) -> Set[GraphId]:
        if self._source_dependants is None:
            g = self.graph
            bad: Set[GraphId] = set(g.sources)
            for s in g.sources:
                from keystone_tpu.workflow.graph import get_descendants

                bad |= get_descendants(g, s)
            self._source_dependants = bad
        return self._source_dependants

    def execute(self, graph_id: GraphId) -> Expression:
        if graph_id in self._unexecutable():
            raise ValueError(
                f"{graph_id} depends on an unconnected source; splice data in "
                "with pipeline.apply(...) before executing"
            )
        if graph_id in self._execution_state:
            return self._execution_state[graph_id]

        g, prefixes = self._optimized_graph_and_prefixes()
        if isinstance(graph_id, SourceId):
            raise ValueError(f"cannot execute source {graph_id}")
        if isinstance(graph_id, SinkId):
            expr = self.execute(g.sink_dependencies[graph_id])
        else:
            dep_exprs = [self.execute(d) for d in g.dependencies[graph_id]]
            op = g.operators[graph_id]
            expr = op.execute(dep_exprs)
            if not isinstance(op, ExpressionOperator):
                # a saved expression was made, and spanned, by another node
                _span_node(expr, op, graph_id, dep_exprs)
            # Cross-pipeline prefix memoization (GraphExecutor.scala:68-70):
            # expose this node's expression under its structural prefix.
            prefix = prefixes.get(graph_id)
            if prefix is not None:
                PipelineEnv.get_or_create().state.setdefault(prefix, expr)
        self._execution_state[graph_id] = expr
        return expr


def _span_node(expr: Expression, op, graph_id, dep_exprs) -> None:
    """Put the node's own work, which runs when ``expr`` is first asked
    for, inside its ``node:<label>`` span, its dependencies forced
    before the span opens."""
    label = getattr(op, "label", type(op).__name__)

    def run(thunk):
        for d in dep_exprs:
            d.get()
        with span(f"node:{label}", node_id=str(graph_id)):
            return thunk()

    expr.around(run)
