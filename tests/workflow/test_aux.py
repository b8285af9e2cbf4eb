"""Aux subsystem tests: prefix-state persistence, profiling hooks, CLI,
DOT export, external NLP wrappers."""

import subprocess
import sys

import numpy as np
import pytest

from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.workflow.api import Pipeline, Transformer
from keystone_tpu.workflow.executor import PipelineEnv


import dataclasses

from keystone_tpu.workflow.api import Estimator


@dataclasses.dataclass(eq=False)
class _Demean(Transformer):
    """Module-level so FittedPipeline/state pickling works."""

    mu: float

    def apply(self, x):
        return x - self.mu


_FIT_CALLS = {"n": 0}


@dataclasses.dataclass(eq=False)
class _MeanEstimator(Estimator):
    def fit(self, data):
        _FIT_CALLS["n"] += 1
        return _Demean(float(np.asarray(data.array()).mean()))

    def eq_key(self):
        return ("mean_estimator",)


def test_prefix_state_persistence_across_reset(tmp_path, mesh8):
    """Fit once, persist, reset (simulating a new process), reload —
    the refit must be skipped (reference guarantee: 'Do not fit
    estimators multiple times' + FittedPipeline save/load)."""
    calls = _FIT_CALLS
    calls["n"] = 0
    MeanEstimator = _MeanEstimator

    data = Dataset.of(np.ones((8, 2), np.float32) * 5)
    est = MeanEstimator()
    pipe = est.with_data(data)
    out1 = pipe.apply(np.zeros((4, 2), np.float32)).get()
    assert calls["n"] == 1

    env = PipelineEnv.get_or_create()
    path = tmp_path / "state"  # save_state writes a directory
    env.save_state(str(path))
    env.reset()

    n = env.load_state(str(path))
    assert n >= 1
    # rebuild the same pipeline structure over the same data object
    pipe2 = MeanEstimator().with_data(data)
    out2 = pipe2.apply(np.zeros((4, 2), np.float32)).get()
    assert calls["n"] == 1  # loaded state: no refit
    np.testing.assert_allclose(
        np.asarray(out1.array()), np.asarray(out2.array())
    )


def test_auto_cache_profile_spans_and_instrumentation(mesh8):
    from keystone_tpu.observability.tracing import (
        disable_tracing,
        enable_tracing,
        get_tracer,
    )
    from keystone_tpu.ops.stats import LinearRectifier
    from keystone_tpu.workflow.auto_cache import profile_nodes
    from keystone_tpu.workflow.graph import EMPTY_GRAPH
    from keystone_tpu.workflow.operators import DatasetOperator

    pipe = LinearRectifier(0.0).to_pipeline()
    result = pipe.apply(np.ones((4, 3), np.float32))
    ds = Dataset.of(np.ones((8, 2), np.float32))
    graph, data = EMPTY_GRAPH.add_node(DatasetOperator(ds), ())
    graph, node = graph.add_node(LinearRectifier(0.0), (data,))
    graph, _ = graph.add_sink(node)
    tracer = enable_tracing()
    tracer.clear()
    try:
        profiles = profile_nodes(graph, [data, node], scales=(2, 4))
        result.get()
    finally:
        disable_tracing()
    spans = get_tracer().recent()
    # the optimizer's own cost: one span a scale pass, and no second timer
    passes = [s for s in spans if s.name == "auto_cache.profile"]
    assert [s.attrs["scale"] for s in passes] == [2, 4]
    assert all(s.attrs["nodes"] == 2 and s.duration_s > 0 for s in passes)
    assert profiles[node].ns >= 0
    # per-node wall time is the node span's duration: one span per node
    # that did work, opened around the node's own batch_transform
    nodes = [s for s in spans if s.name.startswith("node:")]
    assert [s.name for s in nodes] == ["node:LinearRectifier"]
    assert nodes[0].duration_s > 0 and nodes[0].attrs["node_id"]


def test_dot_export(mesh8):
    from keystone_tpu.ops.stats import LinearRectifier, NormalizeRows

    pipe = LinearRectifier(0.0).and_then(NormalizeRows())
    dot = pipe.to_dot()
    assert "digraph" in dot


def test_cli_help():
    from keystone_tpu.__main__ import main

    assert main(["--help"]) == 0
    assert main(["NoSuchApp"]) == 2


def test_external_nlp_wrappers():
    from keystone_tpu.ops.nlp.external import (
        NER,
        CoreNLPFeatureExtractor,
        POSTagger,
    )

    # defaults work out of the box (rule-based annotators)
    assert POSTagger().apply(["hello"]) == [("hello", "NN")]
    tagged = POSTagger(annotator=lambda ts: ["X"] * len(ts)).apply(
        ["a", "b"]
    )
    assert tagged == [("a", "X"), ("b", "X")]
    assert NER().apply(["hello"]) == ["O"]
    grams = CoreNLPFeatureExtractor(orders=[1]).apply("Dogs running fast")
    assert ["dog"] in grams or ["dogs"] in grams


def test_optimizer_rule_trace_logging(caplog):
    """Each effective rule application logs a node-count delta (reference:
    RuleExecutor.scala:44-50 logs the plan after every rule)."""
    import logging

    from keystone_tpu.ops.stats import LinearRectifier, NormalizeRows
    from keystone_tpu.parallel.dataset import Dataset

    # two identical branches -> CSE has something to merge
    a = LinearRectifier(0.0).and_then(NormalizeRows())
    b = LinearRectifier(0.0).and_then(NormalizeRows())
    from keystone_tpu.workflow.api import Pipeline

    pipe = Pipeline.gather([a, b])
    with caplog.at_level(logging.INFO, logger="keystone_tpu.workflow.rules"):
        import numpy as np

        pipe.apply(Dataset.from_array(np.ones((4, 3), np.float32))).get()
    merges = [
        r for r in caplog.records if "EquivalentNodeMergeRule" in r.message
    ]
    assert merges, "CSE merge should have been logged"
    assert "-> " in merges[0].getMessage()


def test_save_state_large_arrays_per_file_and_budget(tmp_path):
    """Large arrays persist to individual .npy files (streamed, not one
    monolithic pickle) and max_total_bytes drops over-budget entries."""
    import os

    from keystone_tpu.workflow.executor import PipelineEnv
    from keystone_tpu.workflow.expressions import DatasetExpression
    from keystone_tpu.parallel.dataset import Dataset

    env = PipelineEnv.get_or_create()
    big = np.ones((600, 600), np.float32)  # 1.44 MB > 1 MB threshold
    small = np.ones((4, 4), np.float32)
    env.state["bigp"] = DatasetExpression.of(Dataset.from_array(big))
    env.state["smallp"] = DatasetExpression.of(Dataset.from_array(small))
    # force both
    env.state["bigp"].get(); env.state["smallp"].get()

    d = tmp_path / "state"
    env.save_state(str(d))
    npys = [f for f in os.listdir(d) if f.endswith(".npy")]
    assert len(npys) == 1  # only the big array got its own file
    env.reset()
    assert env.load_state(str(d)) == 2
    restored = env.state["bigp"].get().padded()
    np.testing.assert_allclose(np.asarray(restored), big)

    # budget smaller than the big array: entry dropped, small kept
    env.reset()
    env.state["bigp"] = DatasetExpression.of(Dataset.from_array(big))
    env.state["smallp"] = DatasetExpression.of(Dataset.from_array(small))
    env.state["bigp"].get(); env.state["smallp"].get()
    d2 = tmp_path / "state2"
    env.save_state(str(d2), max_total_bytes=1 << 20)
    env.reset()
    assert env.load_state(str(d2)) == 1
    assert "smallp" in env.state and "bigp" not in env.state
