"""The controls and the planted faults, at sizes a test run can hold.

Each cell's control (its reference one precision step down, put in the
program's place) has to read above the cell's limit, and a run whose timed
path is broken underneath has to come out with ``correct`` false, once for
each fault the cell can have. The limits here are the tiny cells' own
(tests/tiny/*.json): the full-size limits were set on the chip (PERF.md).
"""

import numpy as np
import pytest

from benchmark import run
from benchmark.tests.test_dry_run import MANIFEST, tiny


def drive(cell_name, seed=7):
    """A run up to the sample, the look for a chip skipped."""
    cell, config, workload = tiny(cell_name)
    ctx = run.Context(cell, config, workload, seed, 0.2, False)
    reference, sample = run.drive_to_sample(ctx, require_chip=False)
    return ctx, workload, reference, sample


@pytest.mark.parametrize("cell_name,precision", [
    ("timit-fit", "high"), ("flagship-score", "bfloat16")])
def test_control_comes_out_not_correct(cell_name, precision):
    ctx, workload, reference, sample = drive(cell_name)
    ok = reference.compare(ctx, sample)
    low = reference.control(ctx, sample, precision)
    for name, limit in workload["limits"].items():
        assert ok[name] <= limit
    assert any(low[name] > limit for name, limit in workload["limits"].items()), low


def broken_run(cell_name, monkeypatch, target, replacement):
    module = __import__("benchmark.programs." + target[0], fromlist=["x"])
    monkeypatch.setattr(module, target[1], replacement(getattr(module, target[1])))
    cell, config, workload = tiny(cell_name)
    return run.run_cell(MANIFEST, cell, config, workload, seed=13,
                        seconds=0.2, trace=False, require_chip=False)


def timit_unchanged(fit):
    """A step that returns its state unchanged: the model stays zero."""
    def broken(inputs):
        fitted = fit(inputs)
        for op in fitted.graph.operators.values():
            if hasattr(op, "W") and hasattr(op, "block_size"):
                op.W = op.W * 0.0
        return fitted
    return broken


def timit_half_batch(fit):
    """Half of the rows left out, the means taken over the rest."""
    def broken(inputs):
        from keystone_tpu.loaders.csv_loader import LabeledData
        from keystone_tpu.parallel.dataset import Dataset

        half = dict(inputs)
        n = inputs["rows"] // 2
        half["train"] = LabeledData(
            labels=Dataset.from_array(inputs["y"][:n]),
            data=Dataset.from_array(inputs["x"][:n]))
        return fit(half)
    return broken


def timit_altered(outputs):
    """An answer altered where it is produced: one class's scores move
    by a thousandth of the scores' spread."""
    def broken(fitted, inputs):
        out = np.array(outputs(fitted, inputs))
        out[:, 0] += 1e-2 * np.std(out)
        return out
    return broken


def flagship_altered(score):
    def broken(inputs, which="predictor"):
        out = np.array(score(inputs, which))
        if which == "scorer":
            out[:, 3] += 1e-2 * np.std(out)
        return out
    return broken


def flagship_topk_altered(score):
    """The window's own top-5 altered: it no longer is the top-5 of the
    scores it was taken from."""
    def broken(inputs, which="predictor"):
        out = np.array(score(inputs, which))
        if which == "predictor":
            out[0, 0], out[0, 1] = out[0, 1], out[0, 0]
        return out
    return broken


@pytest.mark.parametrize("cell_name,target,replacement", [
    ("timit-fit", ("timit", "fit"), timit_unchanged),
    ("timit-fit", ("timit", "fit"), timit_half_batch),
    ("timit-fit", ("timit", "outputs"), timit_altered),
    ("flagship-score", ("flagship", "score"), flagship_altered),
    ("flagship-score", ("flagship", "score"), flagship_topk_altered),
])
def test_broken_timed_path_is_not_correct(cell_name, target, replacement,
                                          monkeypatch):
    result = broken_run(cell_name, monkeypatch, target, replacement)
    assert result["correct"] is False, result["compared"]


def test_sound_run_is_correct():
    for cell_name in ("timit-fit", "flagship-score"):
        cell, config, workload = tiny(cell_name)
        result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                              seconds=0.2, trace=False, require_chip=False)
        assert result["correct"] is True, result["compared"]
