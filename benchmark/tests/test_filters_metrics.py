"""PR 34's four metric files (layer Workflow): each loads, reads the
trace recorded on the chip — which has neither the span nor the module
— as nothing without raising, reads a hand-made summary by hand, and
the device metric's pattern takes the parent's gather module and the
change's whole filter program, and no other module of the cells."""

import importlib
import os
import re
import types

import pytest

from benchmark import run, trace

MS = 1_000_000
RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")
NAMES = ["filters_host_ms_per_fit.cfit", "filters_host_ms_per_fit.kfit",
         "filters_device_ms_per_fit.cfit", "filters_device_ms_per_fit.kfit"]
# modules of the two cells' traced runs (PERF.md §5), parent and change
PARENT_MODULES = ["jit__gather_patches(1234)", "jit__run_chunk(77)",
                  "jit__block_step(5)", "jit__prep(3)", "jit_svd(9)",
                  "jit__random_crops(11)", "jit__krr_epoch_scan(12)"]
CHANGE_MODULES = ["jit__filter_bank(4321)"] + PARENT_MODULES[1:]


def read(name, ctx):
    spec = run.load_json(run.HERE, "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def context(summary, steps=2):
    return types.SimpleNamespace(trace_summary=summary,
                                 window={"steps": steps, "work": steps * 8})


def summary(modules):
    """Two fits of 50 ms: each module runs 4 ms a fit, back to back, and
    the filters' span is open for the first 10 ms of a fit with a
    workflow span of 2 ms nested in the first."""
    events = []
    for fit in range(2):
        t = fit * 50 * MS
        for name in modules:
            events.append((t, t + 4 * MS, name))
            t += 4 * MS
    host = [(0, 100 * MS, "bench:window"),
            (0, 10 * MS, "ks:cifar.filters"),
            (1 * MS, 3 * MS, "ks:workflow.to_array"),
            (50 * MS, 60 * MS, "ks:cifar.filters")]
    return trace.TraceSummary({0: list(events)}, {0: list(events)}, host, 1)


@pytest.mark.parametrize("name", NAMES)
def test_manifest_entry_and_file(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    cell = "cifar-fit" if name.endswith(".cfit") else "cifar-krr-fit"
    assert entry["workloads"] == [cell]
    assert entry["layer"] == "Workflow" and entry["moves"] == "fit_rows_per_s"
    assert entry["unit"] == "ms/fit" and entry["better"] == "lower"
    assert entry["source"] == (
        "program_span" if "_host_" in name else "device_trace")
    assert MANIFEST["per_layer"].index(entry) >= len(MANIFEST["per_layer"]) - 4


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_where_there_is_nothing(name):
    """The recorded chip trace (no such span, no such module), a CPU
    run (no summary) and a window of no steps: None, never 0."""
    recorded = trace.parse(RECORDED, 1)
    assert recorded is not None
    assert read(name, context(recorded)) is None
    assert read(name, context(None)) is None
    assert read(name, context(summary(CHANGE_MODULES), steps=0)) is None


@pytest.mark.parametrize("modules", [PARENT_MODULES, CHANGE_MODULES],
                         ids=["parent", "change"])
@pytest.mark.parametrize("tag", ["cfit", "kfit"])
def test_reads_by_hand(tag, modules):
    ctx = context(summary(modules))
    # one matching module of 4 ms a fit; 10 ms of span less 2 ms nested
    # in the first fit's: (8 + 10) / 2
    assert read("filters_device_ms_per_fit." + tag, ctx) == pytest.approx(4.0)
    assert read("filters_host_ms_per_fit." + tag, ctx) == pytest.approx(9.0)


@pytest.mark.parametrize("tag", ["cfit", "kfit"])
def test_device_pattern_takes_the_gather_and_the_bank_alone(tag):
    spec = run.load_json(run.HERE, "metrics",
                         "filters_device_ms_per_fit." + tag + ".json")
    assert spec["args"]["line"] == "modules"
    rx = re.compile(spec["args"]["pattern"])
    assert [m for m in PARENT_MODULES if rx.search(m)] == [PARENT_MODULES[0]]
    assert [m for m in CHANGE_MODULES if rx.search(m)] == [CHANGE_MODULES[0]]


def test_the_program_s_module_is_the_one_the_pattern_names():
    """The jitted function's name, as the trace's module line carries
    it (``jit_<name>``), and no other function of the module matches."""
    from keystone_tpu.pipelines.images import random_patch_cifar as app

    spec = run.load_json(run.HERE, "metrics",
                         "filters_device_ms_per_fit.cfit.json")
    rx = re.compile(spec["args"]["pattern"])
    assert rx.search("jit_" + app._filter_bank.__name__)
    assert not rx.search("jit_" + app._gather_windows.__name__)
