"""Compilation seen from inside the program (parallel/runtime.py:
install_compile_telemetry, compile_log): the ``keystone_runtime_*``
families, the bounded log and the ``runtime.trace`` / ``.lower`` /
``.compile`` spans, held against jax's own events on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring
from jax._src import monitoring as monitoring_src
from compile_cache_fixtures import register_listeners, unregister_listeners
from profiler_events import parent_names, profiled

from keystone_tpu.observability.registry import (
    get_global_registry,
    reset_global_registry,
)
from keystone_tpu.observability.tracing import (
    disable_tracing,
    enable_tracing,
    span,
)
from keystone_tpu.parallel import runtime
from keystone_tpu.parallel.dataset import Dataset

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_KNOBS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def telemetry(monkeypatch):
    """A fresh listener set, log and registry for one test; the
    process's own set is put back afterwards."""
    before = runtime._telemetry
    if before is not None:
        unregister_listeners(before)
    monkeypatch.setattr(runtime, "_telemetry", None)
    reset_global_registry()
    runtime.install_compile_telemetry()
    mine = runtime._telemetry
    yield mine
    unregister_listeners(mine)
    if before is not None:
        register_listeners(before)
    reset_global_registry()


@pytest.fixture
def scratch_cache(tmp_path):
    """jax's persistent cache in a directory of this test's own."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {name: getattr(jax.config, name) for name in _CACHE_KNOBS}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _samples(family):
    for fam in get_global_registry().collect():
        if fam.name == family:
            return {
                tuple(sorted(s.labels.items())): s.value for s in fam.samples
            }
    return None


def _total(family, **labels):
    samples = _samples(family)
    return sum(
        v for k, v in samples.items()
        if all(dict(k).get(name) == want for name, want in labels.items())
    )


def _mine(listeners, telemetry):
    return [fn for fn in listeners
            if getattr(fn, "__self__", None) is telemetry]


def test_install_is_idempotent_and_registers_its_families(telemetry):
    runtime.install_compile_telemetry()
    runtime.install_compile_telemetry()
    assert runtime._telemetry is telemetry
    for listeners in (monitoring_src.get_scalar_listeners(),
                      monitoring_src.get_event_listeners(),
                      monitoring_src.get_event_duration_listeners()):
        assert len(_mine(listeners, telemetry)) == 1
    # registered at install: a process that compiled nothing reads 0,
    # not nothing
    for family in ("keystone_runtime_compile_requests_total",
                   "keystone_runtime_backend_seconds_total",
                   "keystone_runtime_trace_lower_seconds_total",
                   "keystone_runtime_listener_errors_total"):
        assert _samples(family) == {}, family
    assert runtime.compile_log() == []


def test_setup_compilation_cache_installs_the_listeners(monkeypatch):
    before = runtime._telemetry
    if before is not None:
        unregister_listeners(before)
    monkeypatch.setattr(runtime, "_telemetry", None)
    # the cache itself is set up already or not: either way the call
    # installs the listeners, once
    monkeypatch.setattr(runtime, "_cache_dir", "/already/decided")
    try:
        assert runtime.setup_compilation_cache() == "/already/decided"
        mine = runtime._telemetry
        assert mine is not None
        assert runtime.setup_compilation_cache() == "/already/decided"
        assert runtime._telemetry is mine
        assert len(_mine(monitoring_src.get_scalar_listeners(), mine)) == 1
    finally:
        unregister_listeners(runtime._telemetry)
        if before is not None:
            register_listeners(before)


def test_compiled_at_first_call_cache_hit_after_clear(
    telemetry, scratch_cache
):
    @jax.jit
    def halve_and_sum(x):
        return (x * 0.5).sum()

    x = np.arange(12.0, dtype=np.float32)
    assert float(halve_and_sum(x)) == 33.0
    first = [r for r in runtime.compile_log() if r["phase"] == "compile"]
    assert [(r["fun_name"], r["outcome"]) for r in first] == [
        ("jit(halve_and_sum)", "compiled")
    ]
    jax.clear_caches()
    assert float(halve_and_sum(x)) == 33.0
    second = [r for r in runtime.compile_log()
              if r["phase"] == "compile"][len(first):]
    assert [(r["fun_name"], r["outcome"]) for r in second] == [
        ("jit(halve_and_sum)", "cache_hit")
    ]
    requests = "keystone_runtime_compile_requests_total"
    assert _total(requests, outcome="compiled") == 1
    assert _total(requests, outcome="cache_hit") == 1
    seconds = "keystone_runtime_backend_seconds_total"
    assert _total(seconds, outcome="compiled") == pytest.approx(
        first[0]["seconds"])
    assert _total(seconds, outcome="cache_hit") == pytest.approx(
        second[0]["seconds"])
    for record in first + second:
        assert record["seconds"] > 0 and record["start_s"] > 1e9
        assert record["owner"] == "other" and record["where"] == ""


def test_nested_traces_are_counted_once(telemetry):
    @jax.jit
    def inner(x):
        return (x @ x).sum()

    @jax.jit
    def outer(x):
        return inner(x) + jnp.sum(x)

    outer(np.ones((8, 8), np.float32)).block_until_ready()
    traces = [r for r in runtime.compile_log() if r["phase"] == "trace"]
    by_name = {r["fun_name"]: r for r in traces}
    assert {"outer", "inner"} <= set(by_name)
    whole = by_name["outer"]
    nested = [r for r in traces if r is not whole]
    # every other trace of this call ran inside outer's
    assert all(
        whole["start_s"] <= r["start_s"]
        and r["start_s"] + r["seconds"]
        <= whole["start_s"] + whole["seconds"] + 1e-3
        for r in nested
    )
    counted = _total(
        "keystone_runtime_trace_lower_seconds_total", phase="trace")
    assert counted == pytest.approx(whole["seconds"], rel=1e-6, abs=1e-9)
    assert counted < sum(r["seconds"] for r in traces)
    assert whole["self_seconds"] < whole["seconds"]


def test_owner_is_who_asked(telemetry):
    @jax.jit
    def double(x):
        return x * 2.0

    # asked by a frame of the package: Dataset.map_arrays calls it
    Dataset.of(np.ones((8, 2), np.float32)).map_arrays(double)
    asked = [r for r in runtime.compile_log()
             if r["fun_name"] in ("double", "jit(double)")]
    assert [r["phase"] for r in asked] == ["trace", "lower", "compile"]
    assert {r["owner"] for r in asked} == {"program"}
    assert {r["where"] for r in asked} == {
        "keystone_tpu.parallel.dataset:Dataset.map_arrays"
    }
    # the same function at another shape, asked by the test itself
    double(np.ones((3,), np.float32))
    mine = [r for r in runtime.compile_log()
            if r["fun_name"] in ("double", "jit(double)")][len(asked):]
    assert [r["phase"] for r in mine] == ["trace", "lower", "compile"]
    assert {r["owner"] for r in mine} == {"other"}
    assert {r["where"] for r in mine} == {""}
    requests = "keystone_runtime_compile_requests_total"
    assert _total(requests, owner="program") >= 1
    assert _total(requests, owner="other") >= 1


def test_a_listener_that_raises_does_not_break_the_compile(
    telemetry, monkeypatch
):
    def broken(frame):
        raise RuntimeError("no stack today")

    monkeypatch.setattr(runtime, "_asker", broken)

    @jax.jit
    def triple(x):
        return x * 3.0

    out = triple(np.ones((4,), np.float32))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    errors = "keystone_runtime_listener_errors_total"
    assert _total(errors, listener="on_entry") >= 3  # trace, lower, compile
    # nothing was opened, so nothing was counted or logged
    assert _total("keystone_runtime_compile_requests_total") == 0
    assert runtime.compile_log() == []
    monkeypatch.undo()


def test_events_without_fun_name_and_the_bounded_log(telemetry):
    telemetry.on_entry(COMPILE, 1_700_000_000.0)
    telemetry.on_event("/jax/compilation_cache/cache_hits")
    telemetry.on_exit(COMPILE, 0.25)
    # an exit whose entry was never seen, and events of other kinds
    telemetry.on_exit(TRACE, 0.5, fun_name="late")
    telemetry.on_entry("/jax/some/other/scalar", 3)
    telemetry.on_exit("/jax/compilation_cache/cache_retrieval_time_sec", 9.0)
    log = runtime.compile_log()
    assert [(r["fun_name"], r["phase"], r["outcome"], r["seconds"])
            for r in log] == [("", "compile", "cache_hit", 0.25)]
    assert log[0]["start_s"] == 1_700_000_000.0
    assert _total("keystone_runtime_listener_errors_total") == 0
    for i in range(runtime.COMPILE_LOG_CAPACITY + 50):
        telemetry.on_entry(TRACE, 1.0, fun_name=f"f{i}")
        telemetry.on_exit(TRACE, 0.001, fun_name=f"f{i}")
    log = runtime.compile_log()
    assert len(log) == runtime.COMPILE_LOG_CAPACITY
    assert log[-1]["fun_name"] == f"f{runtime.COMPILE_LOG_CAPACITY + 49}"


def test_debugz_serves_the_log(telemetry):
    import json

    from keystone_tpu.observability import flight

    @jax.jit
    def negate(x):
        return -x

    negate(np.ones((5,), np.float32))
    code, doc = flight.debugz_document(None)
    assert code == 200
    served = json.loads(json.dumps(doc))["compile_log"]
    assert served == runtime.compile_log()
    assert ("jit(negate)", "compile") in {
        (r["fun_name"], r["phase"]) for r in served}


def test_a_compile_opens_spans_nested_in_the_span_around_it(
    telemetry, tmp_path
):
    @jax.jit
    def shift(x):
        return x + 1.0

    x = np.ones((6,), np.float32)

    def work():
        with span("solver.prep"):
            shift(x).block_until_ready()
        with span("solver.block_step"):
            shift(x).block_until_ready()  # warm: no event, no span

    tracer = enable_tracing()
    tracer.clear()
    try:
        events = profiled(tmp_path, work)
    finally:
        disable_tracing()
    ring = tracer.recent()
    tracer.clear()
    names = [n for _, _, n in events]
    parents = {}
    for name, parent in zip(names, parent_names(events)):
        parents.setdefault(name, parent)  # of a name's first event
    # shift's trace holds its callee's (add): nested, not beside it
    assert names.count("ks:runtime.trace") >= 1
    for phase in ("lower", "compile"):
        assert names.count("ks:runtime." + phase) == 1, names
    for phase in ("trace", "lower", "compile"):
        assert parents["ks:runtime." + phase] == "ks:solver.prep"
    assert names[-1] == "ks:solver.block_step"
    # the ring's copy carries the parent link /tracez shows
    by_name = {s.name: s for s in ring}
    compiled = by_name["runtime.compile"]
    assert compiled.parent_id == by_name["solver.prep"].span_id
    assert compiled.attrs == {"fun": "jit(shift)", "outcome": "compiled"}
    assert by_name["runtime.trace"].attrs == {"fun": "shift"}


def test_counts_equal_the_benchmarks_compile_counter(
    telemetry, scratch_cache
):
    from benchmark.run import CompileCounter

    beside = CompileCounter()
    beside.install()
    try:
        @jax.jit
        def square(x):
            return x * x

        x = np.ones((7,), np.float32)
        square(x)
        Dataset.of(np.ones((8, 2), np.float32)).map_arrays(square)
        jax.clear_caches()
        square(x)
    finally:
        monitoring.unregister_event_duration_listener(beside._duration)
        monitoring.unregister_event_listener(beside._event)
    requests = "keystone_runtime_compile_requests_total"
    assert beside.requests >= 3
    assert _total(requests) == beside.requests
    assert _total(requests, outcome="cache_hit") == beside.hits >= 1
    assert _total(requests, outcome="compiled") == beside.compiled
