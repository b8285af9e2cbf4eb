"""Cost-based physical operator selection.

Reference semantics: workflow/NodeOptimizationRule.scala +
OptimizableNodes.scala — nodes that declare themselves Optimizable expose a
``default`` implementation plus ``optimize(sample, n_total)`` which inspects a
small sample of their actual input (shape, sparsity, size) and returns the
physical operator to run (e.g. LeastSquaresEstimator picking between L-BFGS,
block coordinate descent, and an exact solve by cost model).
"""

from __future__ import annotations

from typing import Dict, Tuple

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.expressions import (
    DatasetExpression,
    Expression,
)
from keystone_tpu.workflow.graph import (
    Graph,
    NodeId,
    SourceId,
    get_ancestors,
)
from keystone_tpu.workflow.operators import (
    DatasetOperator,
    Operator,
)
from keystone_tpu.workflow.rules import PrefixMap, Rule

DEFAULT_SAMPLE_SIZE = 96


class Optimizable:
    """Mix-in for operators with selectable physical implementations."""

    def optimize(self, samples, n_total: int) -> Operator:
        """``samples``: list of sampled dep values (Datasets for dataset
        deps); ``n_total``: true example count of the first dataset dep."""
        raise NotImplementedError


class _SampleCollector:
    """Executes a node's upstream graph with dataset constants truncated to a
    sample, recording each dataset's true size."""

    def __init__(self, graph: Graph, sample_size: int):
        self.graph = graph
        self.sample_size = sample_size
        self.full_sizes: Dict[NodeId, int] = {}
        self._memo: Dict[NodeId, Expression] = {}

    def execute(self, nid: NodeId) -> Expression:
        if nid in self._memo:
            return self._memo[nid]
        op = self.graph.operators[nid]
        if isinstance(op, DatasetOperator):
            ds = op.dataset
            self.full_sizes[nid] = ds.n
            sample = Dataset.from_items(ds.take(self.sample_size))
            expr: Expression = DatasetExpression.of(sample)
        else:
            deps = [self.execute(d) for d in self.graph.dependencies[nid]]
            expr = op.execute(deps)
        self._memo[nid] = expr
        return expr

    def true_n(self, nid: NodeId) -> int:
        """Best-effort true example count upstream of ``nid``: the size of
        the nearest dataset constant feeding it (transformers preserve n)."""
        op = self.graph.operators[nid]
        if isinstance(op, DatasetOperator):
            return self.full_sizes.get(nid, op.dataset.n)
        for d in self.graph.dependencies[nid]:
            if isinstance(d, NodeId):
                n = self.true_n(d)
                if n >= 0:
                    return n
        return -1


class NodeOptimizationRule(Rule):
    def __init__(self, sample_size: int = DEFAULT_SAMPLE_SIZE):
        self.sample_size = sample_size

    def apply(self, graph: Graph, prefixes: PrefixMap) -> Tuple[Graph, PrefixMap]:
        optimizable = [
            n
            for n in sorted(graph.operators.keys())
            if isinstance(graph.operators[n], Optimizable)
        ]
        if not optimizable:
            return graph, prefixes
        collector = _SampleCollector(graph, self.sample_size)
        for n in optimizable:
            # Nodes fed (transitively) by a source can't be sampled: their
            # input is runtime data not yet spliced in.
            if any(
                isinstance(a, SourceId) for a in get_ancestors(graph, n)
            ):
                continue
            deps = graph.dependencies[n]
            samples = [collector.execute(d) for d in deps if isinstance(d, NodeId)]
            if len(samples) != len(deps):
                continue
            # optimize() inspects DATASET samples; a datum-fed node (e.g.
            # a transformer applied to single test items) keeps its
            # default — the reference's rule only matches DatasetExpression
            # inputs (NodeOptimizationRuleSuite: "the optimizable
            # transformer should use the default on test data")
            if not all(isinstance(s, DatasetExpression) for s in samples):
                continue
            sample_values = [s.get() for s in samples]
            n_total = collector.true_n(deps[0]) if deps else -1
            new_op = graph.operators[n].optimize(sample_values, n_total)
            if new_op is not None and new_op is not graph.operators[n]:
                # the node keeps its prefix: the physical choice computes
                # the same value at the same position, so its result is
                # saved and reused like any other estimator fit. Dropping
                # it made every later application of the pipeline refit
                # its optimizable estimators while loading the others
                # from the saved state — a model inconsistent with itself.
                graph = graph.set_operator(n, new_op)
        return graph, prefixes
