"""``weighted-bcd-fit``: the three departures that have to fail, at a
size a test run can hold. Each is read twice: as the reference's
``control`` (the departure solved by the reference and put in the
program's place), and planted in the program under the harness, where the
run has to come out with ``correct`` false. The limits are the tiny
cell's own (tests/tiny/weighted-bcd-fit.json); the full-size ones were set
on the chip (PERF.md section 2)."""

import numpy as np
import pytest

from benchmark import run
from benchmark.programs import flagship_solver
from benchmark.tests.test_controls import drive
from benchmark.tests.test_dry_run import MANIFEST, tiny

CELL = "weighted-bcd-fit"
DEPARTURES = ("bfloat16_features", "no_class_term", "cg_stopped_at_1e-3")


@pytest.fixture(scope="module")
def driven():
    ctx, workload, reference, sample = drive(CELL)
    return workload, reference.compare(ctx, sample), \
        reference.control(ctx, sample)


def test_sound_fit_reads_under_both_limits(driven):
    workload, sound, _ = driven
    assert set(sound) == set(workload["limits"])
    for name, limit in workload["limits"].items():
        assert sound[name] <= limit / 2, (name, sound)


@pytest.mark.parametrize("departure", DEPARTURES)
def test_control_comes_out_not_correct(driven, departure):
    workload, _, low = driven
    assert set(low) == set(DEPARTURES)
    assert any(low[departure][name] > limit
               for name, limit in workload["limits"].items()), low[departure]


def bfloat16_features(build):
    def broken(inputs):
        import jax.numpy as jnp

        from keystone_tpu.parallel.dataset import Dataset

        low = inputs["x"].astype(jnp.bfloat16).astype(jnp.float32)
        return build(dict(inputs, features=Dataset.from_array(low)))
    return broken


def no_class_term(build):
    def broken(inputs):
        import dataclasses

        conf = dataclasses.replace(inputs["conf"], mixture_weight=0.0)
        return build(dict(inputs, conf=conf))
    return broken


def cg_stopped_at_1e_3(build):
    def broken(inputs):
        pipeline = build(inputs)
        for op in pipeline._graph.operators.values():
            if hasattr(op, "pcg_tol"):
                op.pcg_tol = 1e-3
        return pipeline
    return broken


def half_the_rows(build):
    def broken(inputs):
        from keystone_tpu.parallel.dataset import Dataset

        n = inputs["rows"] // 2
        return build(dict(
            inputs, features=Dataset.from_array(inputs["x"][:n]),
            labels=Dataset.from_array(inputs["y"][:n])))
    return broken


@pytest.mark.parametrize("plant", [
    bfloat16_features, no_class_term, cg_stopped_at_1e_3, half_the_rows])
def test_planted_departure_is_not_correct(plant, monkeypatch):
    monkeypatch.setattr(flagship_solver, "build",
                        plant(flagship_solver.build))
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is False, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_altered_scores_are_not_correct(monkeypatch):
    """An answer altered where it is produced: one drawn class's scores
    move by a hundredth of the scores' spread; the model is left alone,
    so only ``scores_rel_err`` can see it."""
    outputs = flagship_solver.outputs

    def broken(fitted, inputs):
        out = outputs(fitted, inputs)
        out["scores"] = out["scores"] + 1e-2 * np.std(out["scores"])
        return out

    monkeypatch.setattr(flagship_solver, "outputs", broken)
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    compared = result["compared"]
    assert result["correct"] is False, compared
    assert compared["scores_rel_err"]["value"] > \
        compared["scores_rel_err"]["limit"]
    assert compared["system_rel_residual"]["value"] <= \
        compared["system_rel_residual"]["limit"]
