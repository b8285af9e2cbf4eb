"""Span tracer: parent links, bounded ring, Chrome trace export."""

import json
import threading

from profiler_events import parent_names as _parent_names
from profiler_events import profiled as _profiled

from keystone_tpu.observability.tracing import (
    DEFAULT_CAPACITY,
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
)


def test_span_nesting_records_parent_links():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        with tr.span("sibling") as sib:
            assert sib.parent_id == outer.span_id
    spans = {s.name: s for s in tr.recent()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["sibling"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    # children finish before their parent
    names = [s.name for s in tr.recent()]
    assert names.index("inner") < names.index("outer")


def test_span_attrs_and_set_attr():
    tr = Tracer()
    with tr.span("work", bucket=8) as sp:
        sp.set_attr("rows", 5)
    (done,) = tr.recent()
    assert done.attrs == {"bucket": 8, "rows": 5}
    assert done.duration_s >= 0


def test_ring_is_bounded():
    tr = Tracer(capacity=10)
    for i in range(25):
        with tr.span(f"s{i}"):
            pass
    spans = tr.recent()
    assert len(spans) == 10
    assert spans[-1].name == "s24"  # most recent kept
    assert tr.recent(3)[0].name == "s22"


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("invisible") as sp:
        sp.set_attr("k", "v")  # no-op, no crash
    assert tr.recent() == []
    assert tr.start_span("also_invisible").span_id is None


def test_parent_links_are_thread_local():
    tr = Tracer()
    seen = {}

    def worker(name):
        with tr.span(name):
            pass

    with tr.span("main_outer"):
        t = threading.Thread(target=worker, args=("other_thread",))
        t.start()
        t.join()
    spans = {s.name: s for s in tr.recent()}
    # the other thread's span must NOT parent under main's open span
    assert spans["other_thread"].parent_id is None
    assert spans["other_thread"].thread_id != spans["main_outer"].thread_id


def test_chrome_trace_structure_loads_as_json(tmp_path):
    tr = Tracer()
    with tr.span("outer", engine="e0"):
        with tr.span("inner"):
            pass
    doc = tr.to_chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert len(events) == 2
    for e in events:
        assert e["ph"] == "X"  # complete events
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "span_id" in e["args"] and "parent_id" in e["args"]
    by_name = {e["name"]: e for e in events}
    assert (
        by_name["inner"]["args"]["parent_id"]
        == by_name["outer"]["args"]["span_id"]
    )
    # inner nests temporally within outer
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]

    path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        reloaded = json.load(f)
    assert reloaded["traceEvents"][0]["name"] in ("outer", "inner")


def test_global_tracer_enable_disable():
    tr = get_tracer()
    assert tr is get_tracer()
    try:
        enable_tracing()
        assert tr.enabled
        with tr.span("global_span"):
            pass
        assert any(s.name == "global_span" for s in tr.recent())
    finally:
        disable_tracing()
        tr.clear()
    assert not tr.enabled


def test_out_of_order_end_is_tolerated():
    tr = Tracer()
    a = tr.start_span("a")
    b = tr.start_span("b")
    tr.end_span(a)  # ended before its child
    tr.end_span(b)
    assert {s.name for s in tr.recent()} == {"a", "b"}


# -- trace ids (request identity across the span tree) ---------------------


def test_trace_id_shared_down_the_tree():
    tr = Tracer()
    with tr.span("root") as root:
        assert root.trace_id is not None and len(root.trace_id) == 32
        with tr.span("child") as child:
            assert child.trace_id == root.trace_id
    spans = {s.name: s for s in tr.recent()}
    assert spans["child"].trace_id == spans["root"].trace_id


def test_separate_roots_get_separate_traces():
    tr = Tracer()
    with tr.span("a") as a:
        pass
    with tr.span("b") as b:
        pass
    assert a.trace_id != b.trace_id


def test_pinned_cross_thread_parent_joins_the_trace():
    """The gateway chain: the admit span ENDS before the coalesce span
    starts on another thread, yet the pinned parent_id must carry the
    trace id across."""
    tr = Tracer()
    with tr.span("gateway.admit") as admit:
        pass  # finished before the dispatcher thread runs

    def dispatcher():
        with tr.span("microbatch.coalesce", parent_id=admit.span_id):
            with tr.span("serving.dispatch"):
                pass

    t = threading.Thread(target=dispatcher)
    t.start()
    t.join()
    spans = {s.name: s for s in tr.recent()}
    assert spans["microbatch.coalesce"].trace_id == admit.trace_id
    assert spans["serving.dispatch"].trace_id == admit.trace_id
    assert tr.spans_for_trace(admit.trace_id) == tr.recent()


def test_unknown_pinned_parent_roots_a_new_trace():
    tr = Tracer()
    with tr.span("orphan", parent_id=999_999_999) as sp:
        pass
    assert sp.trace_id is not None
    (done,) = tr.recent()
    assert done.parent_id == 999_999_999


def test_spans_for_trace_filters_the_ring():
    tr = Tracer()
    with tr.span("t1") as a:
        pass
    with tr.span("t2"):
        pass
    only = tr.spans_for_trace(a.trace_id)
    assert [s.name for s in only] == ["t1"]
    assert tr.spans_for_trace("") == []


def test_chrome_trace_args_carry_trace_id():
    tr = Tracer()
    with tr.span("x") as sp:
        pass
    (event,) = tr.to_chrome_trace()["traceEvents"]
    assert event["args"]["trace_id"] == sp.trace_id


# -- sinks -----------------------------------------------------------------


def test_sink_sees_finished_spans_and_unhooks():
    tr = Tracer()
    seen = []
    tr.add_sink(seen.append)
    with tr.span("observed"):
        pass
    assert [s.name for s in seen] == ["observed"]
    tr.remove_sink(seen.append)
    with tr.span("unobserved"):
        pass
    assert len(seen) == 1


def test_broken_sink_does_not_break_spans():
    tr = Tracer()

    def boom(span):
        raise RuntimeError("exporter bug")

    tr.add_sink(boom)
    with tr.span("survives"):
        pass
    assert [s.name for s in tr.recent()] == ["survives"]


# -- enable_tracing capacity swap vs concurrent writers --------------------


def test_enable_tracing_capacity_swap_is_atomic_with_writers():
    """Regression: enable_tracing(capacity=...) rebuilt the global
    ring via deque(old, maxlen=new) WITHOUT the tracer lock — a
    concurrent end_span could append mid-copy (RuntimeError: deque
    mutated during iteration) or land its span in the doomed old ring.
    The swap now happens under the tracer lock."""
    tr = enable_tracing()
    tr.clear()
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            try:
                span = tr.start_span(f"w{i}")
                tr.end_span(span)
            except Exception as e:  # the pre-fix failure mode
                errors.append(e)
                return
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        # hammer the resize path against the writers
        for round_ in range(200):
            enable_tracing(capacity=64 + (round_ % 2))
    finally:
        stop.set()
        for t in threads:
            t.join()
        enable_tracing(capacity=DEFAULT_CAPACITY)  # for the tests after
        disable_tracing()
        tr.clear()
    assert errors == []


def test_enable_tracing_preserves_recent_spans_across_resize():
    tr = enable_tracing(capacity=8)
    try:
        tr.clear()
        with tr.span("keep-me"):
            pass
        enable_tracing(capacity=16)
        assert any(s.name == "keep-me" for s in tr.recent())
        assert tr._ring.maxlen == 16
    finally:
        enable_tracing(capacity=DEFAULT_CAPACITY)  # for the tests after
        disable_tracing()
        tr.clear()


# -- W3C trace context (the cross-process wire format) ----------------------


def test_traceparent_round_trips():
    from keystone_tpu.observability.tracing import (
        format_traceparent,
        parse_traceparent,
    )

    tid = "0af7651916cd43dd8448eb211c80319c"
    header = format_traceparent(tid, 0x00F067AA0BA902B7)
    assert header == f"00-{tid}-00f067aa0ba902b7-01"
    ctx = parse_traceparent(header)
    assert ctx.trace_id == tid
    assert ctx.parent_span_id == "00f067aa0ba902b7"
    assert ctx.flags == "01"


def test_traceparent_rejects_malformed_and_all_zero():
    from keystone_tpu.observability.tracing import parse_traceparent

    tid = "0af7651916cd43dd8448eb211c80319c"
    bad = [
        None,
        "",
        "garbage",
        f"00-{tid}-00f067aa0ba902b7",          # missing flags
        f"zz-{tid}-00f067aa0ba902b7-01",        # non-hex version
        f"ff-{tid}-00f067aa0ba902b7-01",        # forbidden version
        "00-" + "0" * 32 + "-00f067aa0ba902b7-01",  # zero trace id
        f"00-{tid}-" + "0" * 16 + "-01",        # zero parent id
        f"00-{tid[:30]}-00f067aa0ba902b7-01",   # short trace id
        # version 00 defines EXACTLY four fields; trailing data means
        # restart-the-trace, not adopt-and-ignore
        f"00-{tid}-00f067aa0ba902b7-01-extra",
    ]
    for header in bad:
        assert parse_traceparent(header) is None, header
    # uppercase input normalizes (the spec says lowercase on the wire,
    # receivers are lenient)
    assert parse_traceparent(
        f"00-{tid.upper()}-00F067AA0BA902B7-01"
    ).trace_id == tid


def test_start_span_adopts_explicit_trace_id():
    """An explicit trace_id (an inbound traceparent's) roots the local
    chain under the REMOTE trace: children inherit it through both the
    thread stack and cross-thread parent pinning."""
    from keystone_tpu.observability.tracing import Tracer

    tr = Tracer(enabled=True)
    tid = "ab" * 16
    root = tr.start_span("gateway.admit", trace_id=tid)
    assert root.trace_id == tid
    with tr.span("inner") as inner:
        assert inner.trace_id == tid
        assert inner.parent_id == root.span_id
    tr.end_span(root)
    # cross-thread pinning joins the adopted trace too
    pinned = tr.start_span("microbatch.coalesce", parent_id=root.span_id)
    assert pinned.trace_id == tid
    tr.end_span(pinned)
    assert {s.trace_id for s in tr.spans_for_trace(tid)} == {tid}


def test_disabled_tracer_span_accepts_trace_id():
    from keystone_tpu.observability.tracing import Tracer

    tr = Tracer(enabled=False)
    span = tr.start_span("gateway.admit", trace_id="cd" * 16)
    assert span.trace_id is None  # the shared null span records nothing
    with tr.span("x", trace_id="cd" * 16) as s:
        assert s.trace_id is None
    assert tr.recent() == []


# -- the one span call: the profiler's copy, the ring's copy, the counters --


class _NoLock:
    """Stands in for the tracer's lock: taking it fails the test."""

    def __enter__(self):
        raise AssertionError("span() took the tracer's lock")

    def __exit__(self, *exc):
        return False


def _count(events, name):
    return sum(1 for _, _, n in events if n == name)


def _counter(name):
    from keystone_tpu.observability.registry import get_global_registry

    return get_global_registry().counter(name).get()


def test_span_off_records_nothing_and_takes_no_lock():
    from keystone_tpu.observability import tracing

    tr = Tracer(enabled=False)
    tr._lock = _NoLock()
    with tr.span("solver.host_solve", width=8) as sp:
        sp.set_attr("fallback", "eigh")  # goes nowhere, no crash
        assert sp.span_id is None
    tr.end_span(tr.start_span("router.forward", attempt=1))
    assert list(tr._ring) == []
    # the module-level call is the global tracer's own method
    assert tracing.span == get_tracer().span
    get_tracer().clear()
    disable_tracing()
    with tracing.span("workflow.apply", n=2):
        pass
    assert get_tracer().recent() == []


def test_ring_and_profiler_hold_the_same_names_and_parents(tmp_path):
    from keystone_tpu.observability.tracing import span

    tr = enable_tracing()
    tr.clear()

    def work():
        with span("node:Outer", node_id="n1"):
            with span("workflow.upload", n=3):
                pass
            with span("workflow.apply", n=3):
                with span("solver.prep"):
                    pass
        done = tr.start_span("router.forward")
        tr.end_span(done)

    try:
        events = _profiled(tmp_path, work)
    finally:
        disable_tracing()
    ring = sorted(tr.recent(), key=lambda s: s.span_id)  # start order
    tr.clear()
    by_id = {s.span_id: s.name for s in ring}
    assert [n for _, _, n in events] == ["ks:" + s.name for s in ring]
    assert _parent_names(events) == [
        None if s.parent_id is None else "ks:" + by_id[s.parent_id]
        for s in ring
    ]
    assert _parent_names(events) == [
        None, "ks:node:Outer", "ks:node:Outer", "ks:workflow.apply", None,
    ]


def test_block_ls_host_fit_spans_and_counters(tmp_path, mesh8):
    import numpy as np

    from keystone_tpu.observability.registry import reset_global_registry
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import LinearRectifier
    from keystone_tpu.parallel.dataset import Dataset

    num_iter, blocks = 2, 3
    rng = np.random.default_rng(0)
    x = Dataset.from_array(rng.standard_normal((64, 12)).astype(np.float32))
    y = Dataset.from_array(rng.standard_normal((64, 2)).astype(np.float32))
    pipe = LinearRectifier(-9.0).and_then(
        BlockLeastSquaresEstimator(4, num_iter=num_iter, solve="host"), x, y
    )
    reset_global_registry()
    try:
        events = _profiled(tmp_path, pipe.fit)
        steps = num_iter * blocks
        node = "ks:node:BlockLeastSquaresEstimator"
        assert _count(events, node) == 1
        assert _count(events, "ks:solver.prep") == 1
        parents = dict(zip(events, _parent_names(events)))
        for phase in ("block_stats", "readback", "host_solve", "upload",
                      "residual_update"):
            mine = [e for e in events if e[2] == "ks:solver." + phase]
            assert len(mine) == steps, phase
            assert {parents[e] for e in mine} == {node}, phase
        assert _count(events, "ks:solver.block_step") == 0
        # each block's Gram dispatched ahead once: block 0's after the
        # prep, each other's between the previous block's read-back and
        # its factorisation
        ahead = [e for e in events if e[2] == "ks:solver.gram_ahead"]
        assert {parents[e] for e in ahead} == {node}
        order = [
            n[len("ks:solver."):] for _, _, n in sorted(events)
            if n in ("ks:solver.prep", "ks:solver.gram_ahead",
                     "ks:solver.readback", "ks:solver.host_solve")
        ]
        assert order == [
            "prep", "gram_ahead",
            "readback", "gram_ahead", "host_solve",
            "readback", "gram_ahead", "host_solve",
            "readback", "host_solve",
        ] + ["readback", "host_solve"] * (steps - blocks)
        # node spans follow one another: the features' node is no parent
        assert parents[next(e for e in events if e[2] == node)] is None
        assert _counter("keystone_solver_fits_total") == 1
        # a Gram per block on its first visit, the kept factor after
        assert _counter("keystone_solver_gram_builds_total") == blocks
        assert _counter("keystone_solver_gram_prefetches_total") == (
            blocks - 1
        )
        assert _counter("keystone_solver_factor_reuses_total") == (
            steps - blocks
        )
        assert _counter("keystone_solver_block_steps_total") == steps
        assert _counter("keystone_solver_host_solves_total") == steps
        assert _counter("keystone_solver_host_solve_fallbacks_total") == 0
        # f32: a (4, 4) Gram and a (4, 2) right-hand side on a block's
        # first visit, the right-hand side alone on every later one
        assert _counter("keystone_solver_readback_bytes_total") == (
            blocks * (16 + 8) * 4 + (steps - blocks) * 8 * 4
        )
    finally:
        reset_global_registry()


def test_block_ls_device_fit_opens_block_step_spans(mesh8):
    import numpy as np

    from keystone_tpu.observability.registry import reset_global_registry
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.parallel.dataset import Dataset

    rng = np.random.default_rng(1)
    x = Dataset.from_array(rng.standard_normal((64, 8)).astype(np.float32))
    y = Dataset.from_array(rng.standard_normal((64, 2)).astype(np.float32))
    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        BlockLeastSquaresEstimator(4, num_iter=2, lam=0.1).fit(x, y)
        names = [s.name for s in tr.recent()]
        assert names.count("solver.block_step") == 4
        assert names.count("solver.prep") == 1
        assert "solver.host_solve" not in names
        assert _counter("keystone_solver_gram_builds_total") == 4
        assert _counter("keystone_solver_host_solves_total") == 0
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()


def _bucketed(monkeypatch, items):
    from keystone_tpu.ops.images.core import PixelScaler
    from keystone_tpu.parallel.dataset import Dataset
    from keystone_tpu.workflow import api

    monkeypatch.setattr(api, "BUCKET_CHUNK", 2)
    return lambda: PixelScaler().apply_batch(Dataset.from_items(items))


def test_bucketed_batch_spans_do_not_grow_with_items(tmp_path, monkeypatch):
    import numpy as np

    counts = {}
    for n in (5, 6):
        items = [np.full((4, 4, 3), i, np.uint8) for i in range(n)]
        sub = tmp_path / str(n)
        sub.mkdir()
        events = _profiled(sub, _bucketed(monkeypatch, items))
        # the first pass compiles (``ks:runtime.*``: the runtime's
        # compile spans); what is held here is the workflow's own
        counts[n] = {
            name: _count(events, name) for name in {e[2] for e in events}
            if not name.startswith("ks:runtime.")
        }
    want = {"ks:workflow.upload": 1, "ks:workflow.stack": 3,
            "ks:workflow.apply": 3, "ks:workflow.slice": 3}
    # 5 items at chunk 2 are 3 chunks, and so are 6: one more item, not
    # one more span of any name
    assert counts[5] == want and counts[6] == want


def test_bucketed_batch_counters(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.observability.registry import reset_global_registry

    host = [np.full((4, 4, 3), i, np.uint8) for i in range(5)]
    reset_global_registry()
    try:
        # host items of one shape: one array, one put, no item ever cut
        out = _bucketed(monkeypatch, host)()
        assert out.is_array and len(out.items()) == 5
        assert _counter("keystone_workflow_items_total") == 5
        assert _counter("keystone_workflow_array_items_total") == 5
        assert _counter("keystone_workflow_chunks_total") == 3
        assert _counter("keystone_workflow_padded_rows_total") == 1
        assert _counter("keystone_workflow_item_slices_total") == 0
        assert _counter("keystone_workflow_h2d_items_total") == 5
        assert _counter("keystone_workflow_h2d_transfers_total") == 1
        assert _counter("keystone_workflow_h2d_bytes_total") == 5 * 48
        reset_global_registry()
        _bucketed(monkeypatch, [jnp.asarray(x) for x in host])()
        assert _counter("keystone_workflow_items_total") == 5
        assert _counter("keystone_workflow_array_items_total") == 5
        assert _counter("keystone_workflow_item_slices_total") == 0
        assert _counter("keystone_workflow_h2d_items_total") == 0
        assert _counter("keystone_workflow_h2d_transfers_total") == 0
        reset_global_registry()
        # ragged: one array a shape, a put a group, no item cut until
        # items are asked for (and then a slice each, counted)
        ragged = host[:3] + [np.zeros((2, 6, 3), np.uint8)] * 2
        out = _bucketed(monkeypatch, ragged)()
        assert out.is_grouped and len(out.groups()) == 2
        assert _counter("keystone_workflow_items_total") == 5
        assert _counter("keystone_workflow_array_items_total") == 5
        assert _counter("keystone_workflow_shape_groups_total") == 2
        assert _counter("keystone_workflow_chunks_total") == 2
        assert _counter("keystone_workflow_padded_rows_total") == 0
        assert _counter("keystone_workflow_item_slices_total") == 0
        assert _counter("keystone_workflow_h2d_items_total") == 5
        assert _counter("keystone_workflow_h2d_transfers_total") == 2
        assert _counter("keystone_workflow_h2d_bytes_total") == 3 * 48 + 2 * 36
        assert len(out.items()) == 5
        assert _counter("keystone_workflow_items_total") == 10
        assert _counter("keystone_workflow_item_slices_total") == 5
    finally:
        reset_global_registry()


def test_dataset_item_paths_carry_one_span_each():
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.observability.registry import reset_global_registry
    from keystone_tpu.parallel.dataset import Dataset

    tr = enable_tracing()
    tr.clear()
    reset_global_registry()
    try:
        items = [(np.ones(3, np.float32), jnp.ones(2)) for _ in range(4)]
        arrays = Dataset.from_items(items).to_array_mode()
        # each item is a host leaf and a device leaf: one put an item
        assert _counter("keystone_workflow_h2d_items_total") == 4
        assert _counter("keystone_workflow_h2d_transfers_total") == 4
        assert _counter("keystone_workflow_h2d_bytes_total") == 4 * 12
        arrays.items()
        arrays.map(lambda x: x)
        # (a first run compiles: the runtime's own spans aside)
        names = [s.name for s in tr.recent()
                 if not s.name.startswith("runtime.")]
        assert names.count("workflow.to_array") == 1
        assert names.count("workflow.map_items") == 1
        # items() once for itself, once inside map
        assert names.count("workflow.to_items") == 2
        assert len(names) == 4
    finally:
        disable_tracing()
        tr.clear()
        reset_global_registry()
