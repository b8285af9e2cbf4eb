"""PR 35's reader and four metric files (layer Runtime, moves setup_s):
``counter_sum`` reads a labelled sum or nothing; under the tiny harness
a traced run reports the four metrics, and a compile planted inside the
window shows in ``compiles_in_window`` (the harness's own listener) and
as ``ks:runtime.compile`` spans in the window (the program's), by the
same number."""

import importlib
import types

import numpy as np
import pytest

from benchmark import run, trace
from benchmark.readers import counter_sum
from benchmark.tests.test_dry_run import MANIFEST, tiny

NAMES = ["program_compiles_per_run", "program_compile_s_per_run",
         "program_cache_load_s_per_run", "program_trace_lower_s_per_run"]


@pytest.fixture
def registry():
    from keystone_tpu.observability import registry as registry_lib

    registry_lib.reset_global_registry()
    yield registry_lib.get_global_registry()
    registry_lib.reset_global_registry()


def test_counter_sum_reads_the_labelled_sum_or_nothing(registry):
    ctx = types.SimpleNamespace()
    family = "keystone_runtime_backend_seconds_total"
    assert counter_sum.read(ctx, family, {"owner": "program"}) is None
    counter = registry.counter(family, "", labelnames=("outcome", "owner"))
    # the family is there, nothing of the kind happened: 0, not nothing
    assert counter_sum.read(ctx, family, {"owner": "program"}) == 0.0
    counter.inc(("compiled", "program"), 2.5)
    counter.inc(("cache_hit", "program"), 0.25)
    counter.inc(("compiled", "other"), 7.0)
    assert counter_sum.read(
        ctx, family, {"outcome": "compiled", "owner": "program"}) == 2.5
    assert counter_sum.read(ctx, family, {"owner": "program"}) == 2.75
    assert counter_sum.read(ctx, family, {}) == 9.75
    assert counter_sum.read(ctx, family, {"owner": "nobody"}) == 0.0
    assert counter_sum.read(ctx, "keystone_no_such_total", {}) is None


@pytest.mark.parametrize("name", NAMES)
def test_manifest_entry_and_file(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [w["name"] for w in MANIFEST["workloads"]]
    assert entry["layer"] == "Runtime" and entry["moves"] == "setup_s"
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert entry["unit"] == ("programs" if "compiles" in name else "s")
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    assert spec["reader"] == "counter_sum"
    assert spec["args"]["labels"]["owner"] == "program"
    importlib.import_module("benchmark.readers." + spec["reader"])


def window_compile_spans(trace_dir):
    """``ks:runtime.compile`` spans that begin inside the window (first
    ``bench:step`` to the last one's end) on the benchmark's thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace.find_xplane(trace_dir))
    for plane in data.planes:
        for line in plane.lines:
            events = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                       e.name) for e in line.events]
            steps = [(s, e) for s, e, n in events if n == "bench:step"]
            if steps:
                lo = min(s for s, _ in steps)
                hi = max(e for _, e in steps)
                return sum(1 for s, _, n in events
                           if n == "ks:runtime.compile" and lo <= s < hi)
    raise AssertionError("no bench:step in the trace")


def traced(tmp_path, tag):
    cell, config, workload = tiny("timit-fit")
    keep = tmp_path / tag
    keep.mkdir()
    result = run.run_cell(MANIFEST, cell, config, workload, seed=2 ** 31 + 35,
                          seconds=0.3, trace=True, require_chip=False,
                          keep_trace=str(keep))
    assert result["correct"] is True, result["compared"]
    return result["metrics"], window_compile_spans(str(keep))


def test_four_metrics_reported_and_a_planted_compile_is_seen_twice(
        tmp_path, monkeypatch):
    import jax

    from benchmark.drivers import fit_loop

    clean, clean_spans = traced(tmp_path, "clean")
    for name in NAMES:
        assert clean[name]["value"] >= 0.0, name
        assert clean[name]["unit"] == ("programs" if "compiles" in name
                                       else "s")
    # tiny sizes, this process: the program traced and lowered something
    assert clean["program_trace_lower_s_per_run"]["value"] > 0.0
    assert clean["compiles_in_window.fit"]["value"] == clean_spans

    planted = []
    real = fit_loop.loop_steps

    def loop_with_compiles(ctx, step):
        def compiling(i):
            if i < 2:  # a function the process has not met: one compile
                shift = 1000.5 + len(planted)
                jax.jit(lambda x: x + shift)(
                    np.ones(3, np.float32)).block_until_ready()
                planted.append(i)
            step(i)
        return real(ctx, compiling)

    monkeypatch.setattr(fit_loop, "loop_steps", loop_with_compiles)
    requests = "keystone_runtime_compile_requests_total"
    others = counter_sum.read(None, requests, {"owner": "other"})
    dirty, dirty_spans = traced(tmp_path, "planted")
    assert len(planted) >= 1
    assert dirty["compiles_in_window.fit"]["value"] == (
        clean["compiles_in_window.fit"]["value"] + len(planted))
    assert dirty_spans == clean_spans + len(planted)
    # asked by the harness, not by the program: owner "other"
    assert counter_sum.read(None, requests, {"owner": "other"}) >= (
        others + len(planted))
