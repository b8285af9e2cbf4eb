"""Profile-driven automatic cache placement.

Reference: workflow/AutoCacheRule.scala:12-664 — profile nodes by executing
the graph on sample scales (partitionScales=Seq(2,4), numTrials=1) timing
wall-clock and measuring RDD/driver memory, fit per-node linear models of
time/memory vs scale (generalizeProfiles solves X \\ y), estimate the total
runtime implied by a candidate cache set via per-node run counts weighted
by WeightedNode.weight (number of passes an op makes over its input), then
either AggressiveCache (cache anything used more than once, :503) or
GreedyCache under a memory budget = 75% of cluster-remaining
(greedyCache:559-602, selectNext:542); finally insert Cacher() nodes
(addCachesToPipeline:492).

TPU translation: "RDD memory" is device-buffer bytes (jax arrays report
nbytes), "driver memory" is host-object size, and the default budget is a
fraction of the accelerator's per-device memory.
"""

from __future__ import annotations

import dataclasses
import logging
import sys
import time
from typing import Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.expressions import (
    DatasetExpression,
    Expression,
)
from keystone_tpu.workflow.graph import (
    Graph,
    NodeId,
    SinkId,
    SourceId,
    get_children,
    linearize,
)
from keystone_tpu.workflow.operators import DatasetOperator, Operator
from keystone_tpu.workflow.rules import PrefixMap, Rule

logger = logging.getLogger(__name__)

DEFAULT_SAMPLE_SCALES = (2, 4)  # reference: partitionScales = Seq(2, 4)
DEFAULT_BUDGET_FRACTION = 0.75  # reference: 75% of remaining memory


@dataclasses.dataclass
class Profile:
    """Per-node cost estimate (reference: AutoCacheRule.scala:18
    Profile(ns, rddMem, driverMem))."""

    ns: float  # estimated execution time, nanoseconds
    device_mem: float  # bytes of device-resident output
    host_mem: float  # bytes of host-resident output

    def __add__(self, other: "Profile") -> "Profile":
        return Profile(
            self.ns + other.ns,
            self.device_mem + other.device_mem,
            self.host_mem + other.host_mem,
        )


def _measure_size(value) -> Tuple[float, float]:
    """(device_bytes, host_bytes) of an operator output."""
    if isinstance(value, Dataset):
        if value.is_array:
            leaves = jax.tree_util.tree_leaves(value.padded())
            return float(sum(x.nbytes for x in leaves)), 0.0
        return 0.0, float(
            sum(sys.getsizeof(x) for x in value.items())
        )
    if isinstance(value, jax.Array) or isinstance(value, np.ndarray):
        return float(value.nbytes), 0.0
    return 0.0, float(sys.getsizeof(value))


def get_node_weights(graph: Graph) -> Dict[NodeId, int]:
    """WeightedNode.weight = passes an operator makes over its input
    (reference: AutoCacheRule.getNodeWeights:23)."""
    return {
        n: int(getattr(op, "weight", 1))
        for n, op in graph.operators.items()
    }


def get_runs(
    graph: Graph,
    cache_set: Set[NodeId],
    weights: Dict[NodeId, int],
) -> Dict[NodeId, int]:
    """Times each node's expression is evaluated given the cached set
    (reference: AutoCacheRule.getRuns:57): a cached node evaluates once;
    otherwise once per pass each consumer makes. Sink reads count as one
    weight-1 consumer each."""
    runs: Dict[NodeId, int] = {}
    for n in reversed([g for g in linearize(graph) if isinstance(g, NodeId)]):
        total = 0
        for c in get_children(graph, n):
            if isinstance(c, SinkId):
                total += 1
            elif isinstance(c, NodeId):
                c_runs = 1 if c in cache_set else runs.get(c, 1)
                total += c_runs * weights.get(c, 1)
        runs[n] = max(total, 1)
    return runs


def estimate_cached_runtime(
    graph: Graph,
    cache_set: Set[NodeId],
    profiles: Dict[NodeId, Profile],
    weights: Dict[NodeId, int],
) -> float:
    """Total ns to execute everything given the cache set (reference:
    estimateCachedRunTime:471)."""
    runs = get_runs(graph, cache_set, weights)
    total = 0.0
    for n, p in profiles.items():
        effective = 1 if n in cache_set else runs[n]
        total += p.ns * effective
    return total


class _ScaledProfiler:
    """Executes the graph with dataset constants truncated to n/scale
    examples, timing each operator and measuring outputs (reference:
    profileNodes:153-465)."""

    def __init__(self, graph: Graph, scale: int):
        self.graph = graph
        self.scale = scale
        self.times: Dict[NodeId, float] = {}
        self.sizes: Dict[NodeId, Tuple[float, float]] = {}
        self.sample_n: Dict[NodeId, int] = {}
        self._memo: Dict[NodeId, Expression] = {}

    def execute(self, nid: NodeId) -> Expression:
        if nid in self._memo:
            return self._memo[nid]
        op = self.graph.operators[nid]
        if isinstance(op, DatasetOperator):
            ds = op.dataset
            k = max(1, ds.n // self.scale)
            self.sample_n[nid] = k
            sample = Dataset.from_items(ds.take(k))
            expr: Expression = DatasetExpression.of(sample)
            self.sizes[nid] = _measure_size(sample)
            self.times[nid] = 0.0
        else:
            deps = [self.execute(d) for d in self.graph.dependencies[nid]
                    if isinstance(d, NodeId)]
            if len(deps) != len(self.graph.dependencies[nid]):
                # source-dependent: not profilable
                raise _SourceDependent()
            t0 = time.perf_counter()
            expr = op.execute(deps)
            value = expr.get()  # force
            if isinstance(value, Dataset) and value.is_array:
                jax.block_until_ready(value.padded())
            self.times[nid] = (time.perf_counter() - t0) * 1e9
            self.sizes[nid] = _measure_size(value)
        self._memo[nid] = expr
        return expr


class _SourceDependent(Exception):
    pass


def profile_nodes(
    graph: Graph,
    nodes: List[NodeId],
    scales=DEFAULT_SAMPLE_SCALES,
) -> Dict[NodeId, Profile]:
    """Profile at each scale and linearly extrapolate to full size
    (reference: generalizeProfiles:104 — per-node least squares of
    time/memory vs scale).

    Each scale pass is one ``auto_cache.profile`` span (attrs ``scale``,
    ``nodes``), so the cost the optimizer itself pays to decide cache
    placement is on the profiler's clock and in ``/tracez`` like every
    other span of the package."""
    from keystone_tpu.observability.tracing import span

    per_scale: Dict[int, _ScaledProfiler] = {}
    for scale in scales:
        prof = _ScaledProfiler(graph, scale)
        with span("auto_cache.profile", scale=scale, nodes=len(nodes)):
            for n in nodes:
                try:
                    prof.execute(n)
                except _SourceDependent:
                    continue
        per_scale[scale] = prof

    profiles: Dict[NodeId, Profile] = {}
    for n in nodes:
        xs, ts, dm, hm = [], [], [], []
        for scale, prof in per_scale.items():
            if n in prof.times:
                xs.append(1.0 / scale)  # fraction of full data
                ts.append(prof.times[n])
                d, h = prof.sizes[n]
                dm.append(d)
                hm.append(h)
        if not xs:
            continue
        profiles[n] = Profile(
            _extrapolate(xs, ts), _extrapolate(xs, dm), _extrapolate(xs, hm)
        )
    return profiles


def _extrapolate(fractions: List[float], values: List[float]) -> float:
    """Fit value = a + b·fraction, evaluate at fraction=1 (full scale)."""
    if len(set(fractions)) == 1:
        return values[0] / fractions[0]
    b, a = np.polyfit(fractions, values, 1)
    return float(max(a + b, 0.0))


class AutoCacheRule(Rule):
    def __init__(
        self,
        strategy: str = "greedy",
        mem_budget_bytes: Optional[int] = None,
        scales=DEFAULT_SAMPLE_SCALES,
    ):
        self.strategy = strategy
        self.mem_budget_bytes = mem_budget_bytes
        self.scales = scales

    # -- cache-set selection ----------------------------------------------

    def _budget(self) -> float:
        if self.mem_budget_bytes is not None:
            return float(self.mem_budget_bytes)
        # the shared None-guarded memory_stats probe
        # (observability/device.py — one code path with weighted_ls
        # and the device memory gauges)
        from keystone_tpu.observability.device import device_memory_stats

        stats = device_memory_stats()
        if stats and "bytes_limit" in stats:
            free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            return DEFAULT_BUDGET_FRACTION * free
        return DEFAULT_BUDGET_FRACTION * 8e9  # CPU-host fallback

    def aggressive_cache(
        self, graph: Graph, weights: Dict[NodeId, int]
    ) -> Set[NodeId]:
        """Cache every node whose DIRECT output is consumed more than
        once — Σ over direct children of the child's weight (sinks count
        1) — excluding descendants of sources (test-time data; reference
        AutoCacheRule.aggressiveCache:503-518). NOT the transitive run
        count: a node feeding a single hot consumer is NOT cached (its
        consumer is), matching the reference suite's {+2, +5} selection
        on its 13-node plan."""
        from keystone_tpu.workflow.graph import get_descendants

        source_desc: Set[NodeId] = set()
        for src in graph.sources:
            source_desc |= {
                d for d in get_descendants(graph, src)
                if isinstance(d, NodeId)
            }
        selected: Set[NodeId] = set()
        for n in graph.operators:
            if n in source_desc:
                continue
            total = 0
            for c in get_children(graph, n):
                if isinstance(c, NodeId):
                    total += weights.get(c, 1)
                else:
                    total += 1
            if total > 1:
                selected.add(n)
        return selected

    def greedy_cache(
        self,
        graph: Graph,
        profiles: Dict[NodeId, Profile],
        weights: Dict[NodeId, int],
    ) -> Set[NodeId]:
        """Iteratively cache the node with the best runtime improvement
        until nothing improves or the budget is exhausted (reference:
        greedyCache:559-602, selectNext:542)."""
        budget = self._budget()
        cached: Set[NodeId] = set()
        used = 0.0
        while True:
            base = estimate_cached_runtime(graph, cached, profiles, weights)
            best, best_rt = None, base
            runs = get_runs(graph, cached, weights)
            for n, p in profiles.items():
                # reference selectNext:542 — only nodes still evaluated
                # more than once and fitting the remaining budget
                if (
                    n in cached
                    or runs.get(n, 1) <= 1
                    or p.device_mem + used > budget
                ):
                    continue
                rt = estimate_cached_runtime(
                    graph, cached | {n}, profiles, weights
                )
                if rt < best_rt:
                    best, best_rt = n, rt
            if best is None:
                return cached
            cached.add(best)
            used += profiles[best].device_mem

    # -- graph surgery ----------------------------------------------------

    @staticmethod
    def add_caches(graph: Graph, cache_set: Set[NodeId]) -> Graph:
        """Insert a Cacher() node downstream of each selected node
        (reference: addCachesToPipeline:492)."""
        from keystone_tpu.ops.util.cacher import Cacher

        for n in sorted(cache_set):
            graph, cacher = graph.add_node(Cacher(), ())
            graph = graph.replace_dependency(n, cacher)
            graph = graph.set_dependencies(cacher, (n,))
        return graph

    def apply(self, graph: Graph, prefixes: PrefixMap) -> Tuple[Graph, PrefixMap]:
        from keystone_tpu.ops.util.cacher import Cacher

        weights = get_node_weights(graph)
        already = {
            n for n, op in graph.operators.items() if isinstance(op, Cacher)
        }
        # candidates: nodes not already cached and not feeding a Cacher
        candidates = [
            n
            for n in sorted(graph.operators)
            if n not in already
            and not any(
                isinstance(c, NodeId)
                and isinstance(graph.operators.get(c), Cacher)
                for c in get_children(graph, n)
            )
        ]
        if self.strategy == "aggressive":
            to_cache = self.aggressive_cache(graph, weights) - already
            to_cache = {n for n in to_cache if n in candidates}
        else:
            profiles = profile_nodes(graph, candidates, self.scales)
            if logger.isEnabledFor(logging.INFO):
                for n in sorted(profiles):
                    p = profiles[n]
                    logger.info(
                        "auto-cache profile node %s [%s]: %.1f ms, "
                        "%.0f device bytes, weight %d",
                        n,
                        graph.operators[n].label,
                        p.ns / 1e6,
                        p.device_mem,
                        weights.get(n, 1),
                    )
            to_cache = self.greedy_cache(graph, profiles, weights)
        logger.info(
            "auto-cache decision (%s): caching %s",
            self.strategy,
            sorted(to_cache) or "nothing",
        )
        if not to_cache:
            return graph, prefixes
        return self.add_caches(graph, to_cache), prefixes
