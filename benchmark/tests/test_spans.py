"""The readers of the program's own spans and counters: a summary built
by hand (two device operations, one step, nested ``ks:`` spans, one idle
gap under no ``ks:`` span) against numbers worked out by hand; nothing on
a summary without ``ks:`` spans (the parent commit's trace) and nothing
from a span reader on a CPU run."""

import importlib
import types

import pytest

from benchmark import run, spans, trace

MS = 1_000_000
MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")
SPAN_METRICS = [m["name"] for m in MANIFEST["per_layer"]
                if m["source"] == "program_span"]
COUNTER_RATIOS = ["gram_builds_per_fit.fit", "h2d_transfers_per_image.score"]


def summary(with_spans=True):
    """One 20 ms step. The chip runs 0-4 ms and 10-12 ms, so it idles
    4-10 ms and 12-20 ms. The node span covers 1-16 ms, with block_stats
    1-3 ms, readback 3-5 ms and host_solve 5-11 ms (an upload nested in
    it, 9-10 ms) inside; 16-20 ms lie under the step alone."""
    ops = {0: [(0, 4 * MS, "fusion.gram"), (10 * MS, 12 * MS, "fusion.res")]}
    modules = {0: [(0, 4 * MS, "jit__block_stats"),
                   (10 * MS, 12 * MS, "jit__residual_update")]}
    host = [(0, 20 * MS, "bench:step"), (6 * MS, 7 * MS, "PjitFunction(f)")]
    if with_spans:
        host += [
            (1 * MS, 16 * MS, "ks:node:BlockLeastSquaresEstimator"),
            (1 * MS, 3 * MS, "ks:solver.block_stats"),
            (3 * MS, 5 * MS, "ks:solver.readback"),
            (5 * MS, 11 * MS, "ks:solver.host_solve"),
            (9 * MS, 10 * MS, "ks:solver.upload"),
            # a span of the warm-up, before the window: never counted
            (-9 * MS, -5 * MS, "ks:solver.host_solve"),
        ]
    return trace.TraceSummary(ops, modules, host, 1)


def test_self_times_by_hand():
    s = summary()
    by = spans.self_ns_by_name(s)
    assert by["ks:solver.block_stats"] == (2 * MS, 1)
    assert by["ks:solver.readback"] == (2 * MS, 1)
    # 6 ms less the upload nested in it; the jax event inside stays charged
    assert by["ks:solver.host_solve"] == (5 * MS, 1)
    assert by["ks:solver.upload"] == (1 * MS, 1)
    # 15 ms less the 2 + 2 + 6 of its children
    assert by["ks:node:BlockLeastSquaresEstimator"] == (5 * MS, 1)
    assert spans.span_self_s(s, "solver.host_solve") == pytest.approx(0.005)
    assert spans.span_self_s(s, "solver.nothing") is None


def test_idle_gaps_and_the_unattributed_share_by_hand():
    s = summary()
    assert spans.idle_gaps(s) == [(4 * MS, 10 * MS), (12 * MS, 20 * MS)]
    # the first gap's middle (7 ms) lies in host_solve, the second's
    # (16 ms) at the node span's end, which is outside it: no ks: span
    assert spans.idle_ns_by_span(s) == {
        "ks:solver.host_solve": 6 * MS, spans.NO_SPAN: 8 * MS}
    assert spans.unattributed_idle_share(s) == pytest.approx(8 / 14)
    # cut at the spans' edges: the first gap runs 4-5 ms under readback,
    # 5-9 and 10-10 under host_solve, 9-10 under upload; the second
    # 12-16 ms under the node span and 16-20 ms under none
    assert spans.idle_overlap_ns_by_span(s) == {
        "ks:solver.readback": 1 * MS, "ks:solver.host_solve": 4 * MS,
        "ks:solver.upload": 1 * MS,
        "ks:node:BlockLeastSquaresEstimator": 4 * MS, spans.NO_SPAN: 4 * MS}
    rows = spans.node_table(s)
    assert [r["node"] for r in rows] == ["ks:node:BlockLeastSquaresEstimator"]
    assert rows[0]["count"] == 1 and rows[0]["s"] == pytest.approx(0.015)
    assert rows[0]["idle_s"] == pytest.approx(0.010)  # 4-10 and 12-16 ms
    assert rows[0]["phases"]["ks:solver.host_solve"] == pytest.approx(0.005)


def context(trace_summary, steps=1, work=4):
    return types.SimpleNamespace(trace_summary=trace_summary,
                                 window={"steps": steps, "work": work})


def read(name, ctx):
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def test_span_metrics_through_their_files():
    ctx = context(summary())
    assert read("host_solve_ms_per_fit.fit", ctx) == pytest.approx(5.0)
    assert read("gram_readback_ms_per_fit.fit", ctx) == pytest.approx(2.0)
    assert read("unattributed_idle_pct.fit", ctx) == pytest.approx(800 / 14)
    # no workflow span in this summary: nothing, never 0
    assert read("upload_host_ms_per_image.score", ctx) is None
    assert read("item_slicing_host_ms_per_image.score", ctx) is None
    assert len(SPAN_METRICS) == 6


@pytest.mark.parametrize("name", SPAN_METRICS)
@pytest.mark.parametrize("case", ["parent", "cpu"])
def test_span_metric_reads_nothing_without_spans(name, case):
    """The parent commit's trace has no ``ks:`` span; a CPU run has no
    summary at all. Neither raises, neither reads 0."""
    t = summary(with_spans=False) if case == "parent" else None
    assert read(name, context(t)) is None


@pytest.mark.parametrize("name", COUNTER_RATIOS)
def test_counter_ratio_reads_the_programs_registry(name):
    from keystone_tpu.observability import registry

    spec = run.load_json(run.HERE, "metrics", name + ".json")["args"]
    registry.reset_global_registry()
    try:
        assert read(name, context(None)) is None  # denominator absent
        reg = registry.get_global_registry()
        reg.counter(spec["denominator"]).inc(by=0)
        assert read(name, context(None)) is None  # denominator 0
        reg.counter(spec["denominator"]).inc(by=4)
        assert read(name, context(None)) == 0.0  # a count, not a share
        reg.counter(spec["numerator"]).inc(by=10)
        assert read(name, context(None)) == pytest.approx(2.5)
    finally:
        registry.reset_global_registry()


def test_tool_prints_the_recorded_trace_without_spans(capsys):
    """The recorded chip trace predates the spans: the tool still prints
    its idle gaps, all under no ``ks:`` span."""
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    assert spans.main([path]) == 0
    out = capsys.readouterr().out
    assert "unattributed_idle_share=None" in out and spans.NO_SPAN in out
