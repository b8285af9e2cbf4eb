"""A CPU dry run of every cell at a tiny size: the whole of a run but the
look for a chip, and the result line's keys. CPU runs print no device
metric. The four-chip cell runs on four virtual devices."""

import json

import pytest

from benchmark import run

MANIFEST = run.load_json(run.ROOT, "BENCHMARK.json")


def tiny(cell_name):
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == cell_name)
    config = run.load_json(run.HERE, "tests", "tiny", cell["config"] + ".json")
    base = cell_name[:-3] if cell_name.endswith("-x4") else cell_name
    workload = run.load_json(run.HERE, "tests", "tiny", base + ".json")
    return cell, config, workload


@pytest.mark.parametrize("cell_name",
                         [w["name"] for w in MANIFEST["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_dry_run(cell_name, trace):
    cell, config, workload = tiny(cell_name)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=2 ** 31 + 11,
                          seconds=0.5, trace=trace, require_chip=False)
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    if trace:
        # no device metric from a CPU run: counters only
        sources = {m["name"]: m["source"] for m in MANIFEST["per_layer"]}
        assert all(sources[k] == "program_counter" for k in line["metrics"])
        assert "busy_s" not in line["device"]
    else:
        assert "setup_s" in line["metrics"]
        assert len(line["metrics"]) >= 2
        for name, c in line["compared"].items():
            assert c["value"] <= c["limit"], name


def test_no_chip_no_result(capsys):
    """With the look for a chip on, a CPU has to fail before any work."""
    cell, config, workload = tiny(MANIFEST["workloads"][0]["name"])
    with pytest.raises(run.BenchFailure):
        run.run_cell(MANIFEST, cell, config, workload, seed=1, seconds=0.1,
                     trace=False)
    assert capsys.readouterr().out == ""


def test_every_metric_has_its_files():
    import importlib

    for m in MANIFEST["per_layer"]:
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        importlib.import_module("benchmark.readers." + spec["reader"])
        assert "workloads" in m  # so that a later cell can join it
    for w in MANIFEST["workloads"]:
        run.find_cell(MANIFEST, w["name"])


def test_fit_loop_on_four_virtual_devices():
    """The four-chip TIMIT cell is not in the manifest yet (PERF.md §7),
    but its path is rehearsed: rows sharded over a 4x1 mesh, the
    reference on one device in chunks of rows."""
    _, config, workload = tiny("timit-fit")
    cell = {"name": "timit-fit-x4", "config": "timit-blockls", "chips": 4}
    result = run.run_cell(MANIFEST, cell, config, workload, seed=5,
                          seconds=0.3, trace=False, require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert result["device"]["count"] == 4
