"""The content token of a fitted pipeline (serving/featurize.py:
featurize_token): framed hashing, stability across use, and the graph's
wiring as part of what is hashed."""

import jax.numpy as jnp

from keystone_tpu.serving import featurize
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.serving.featurize import featurize_token


def test_model_token_framing_blocks_adjacent_value_collisions():
    """Unframed hashing folded (1, 23) and (12, 3) to the same bytes;
    a token collision means two different chains taken for one, so
    every hashed component is framed."""
    import hashlib

    def tok(v):
        h = hashlib.sha256()
        featurize._hash_update(h, v)
        return h.hexdigest()

    assert tok([1, 23]) != tok([12, 3])
    assert tok([1, 23]) != tok(["1", 23])
    assert tok({"a": 1, "b": 2}) != tok({"a": 12, "b": ""})
    assert tok([[1], 2]) != tok([[1, 2]])


def test_pipeline_token_stable_across_use_and_distinguishes_weights():
    f1 = build_pipeline(d=8, hidden=8, depth=1)
    before = featurize_token(f1)
    # memoized on the pipeline (N lanes hash the model once, not N
    # times); drop the memo so the recompute below is a REAL one
    assert f1._featurize_token == before
    del f1._featurize_token
    eng = f1.compiled(buckets=(2,))
    eng.warmup(example=jnp.zeros((8,), jnp.float32))
    # lazily-attached operator caches must not shift the token (a
    # token that changed when the pipeline RAN would split one chain
    # into two groups)
    assert featurize_token(f1) == before
    f2 = build_pipeline(d=8, hidden=8, depth=2)
    assert featurize_token(f2) != before


def test_pipeline_token_hashes_graph_wiring():
    """Same operators in the same topo order, DIFFERENT edges: a
    multi-input node fed (A(x), x) vs (A(x), A(x)) computes different
    things, so the tokens must differ — likewise a re-pointed sink."""
    from keystone_tpu.workflow.api import FittedPipeline, Identity
    from keystone_tpu.workflow.graph import Graph

    g0 = Graph(
        sources=frozenset(), sink_dependencies={}, operators={},
        dependencies={},
    )
    g0, src = g0.add_source()
    g0, a = g0.add_node(Identity(), [src])

    # the token only hashes structure + operator identity, so Identity
    # stands in for a real multi-input join here
    g1, j1 = g0.add_node(Identity(), [a, src])
    g1, sink1 = g1.add_sink(j1)
    p1 = FittedPipeline(g1, src, sink1)

    g2, j2 = g0.add_node(Identity(), [a, a])
    g2, sink2 = g2.add_sink(j2)
    p2 = FittedPipeline(g2, src, sink2)

    assert featurize_token(p1) != featurize_token(p2)

    # sink re-pointed from the join back to the first node: same graph
    # body, different exposed value -> different token
    g3, sink3 = g2.add_sink(a)
    p3 = FittedPipeline(g3, src, sink3)
    assert featurize_token(p3) != featurize_token(p2)
