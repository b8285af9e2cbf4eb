"""``mnist-fft-fit``: each control reads above the tiny cell's limit of
the measure it targets, and each fault planted in the program under the
harness comes out with ``correct`` false, at a size a test run can hold
(4 FFTs over 768 rows, blocks of 512 at lambda 0:
tests/tiny/mnist-fft-fit.json, whose limits are the tiny cell's own; the
full-size ones were set on the chip, PERF.md section 2)."""

import numpy as np
import pytest

from benchmark import run
from benchmark.programs import mnist
from benchmark.tests.test_controls import drive
from benchmark.tests.test_dry_run import MANIFEST, tiny

CELL = "mnist-fft-fit"


@pytest.fixture(scope="module")
def driven():
    ctx, workload, reference, sample = drive(CELL)
    return workload, reference.compare(ctx, sample), \
        reference.control(ctx, sample)


def test_sound_fit_reads_under_the_limits(driven):
    workload, sound, _ = driven
    for name, limit in workload["limits"].items():
        assert sound[name] <= limit / 1.5, sound


@pytest.mark.parametrize("departure,measure", [
    ("high_features", "features_rel_err"),
    ("bfloat16_features", "features_rel_err"),
    ("bfloat16_gram", "normal_eq_backward_err"),
    ("bfloat16_gram", "scores_rel_err")])
def test_control_comes_out_not_correct(driven, departure, measure):
    """Each control above the limit of the measure it targets. The Gram
    at ``high`` is left out: at full size its backward error is under
    the program's own float32 solve's (PERF.md section 2)."""
    workload, _, low = driven
    assert low[departure][measure] > 1.5 * workload["limits"][measure], low


def faulty_bank(**change):
    """The application's FFT bank with one departure from PaddedFFT:
    ``flip`` negates branch 0's signs, ``table`` replaces the cosine
    table (the same shape), ``thresh`` the rectifier's threshold."""
    from keystone_tpu.ops.stats import RandomFFTFeatures, nodes

    class Faulty(RandomFFTFeatures):
        @staticmethod
        def create(d, num_ffts, seed=0, rectify_threshold=0.0):
            node = RandomFFTFeatures.create(d, num_ffts, seed=seed)
            signs = node.signs
            if change.get("flip"):
                signs = signs.at[0].multiply(-1.0)
            return Faulty(signs, change.get("thresh", rectify_threshold))

        def _cos(self, d):
            table = change.get("table")
            return nodes._cosines(d, 1024) if table is None else table(d)

    return Faulty


def sines(d):
    """-sin(2π j k / 1024): the imaginary parts in place of the real."""
    jk = (np.arange(d)[:, None] * np.arange(512)[None, :]) % 1024
    return (-np.sin(2.0 * np.pi * jk / 1024)).astype(np.float32)


def unpadded(d):
    """cos(2π j k / d): a DFT of the 784 pixels, no padding to 1,024."""
    jk = (np.arange(d)[:, None] * np.arange(512)[None, :]) % d
    return np.cos(2.0 * np.pi * jk / d).astype(np.float32)


def zero_model(fit):
    def broken(inputs):
        fitted = fit(inputs)
        mnist._model(fitted).W = mnist._model(fitted).W * 0.0
        return fitted
    return broken


@pytest.mark.parametrize("fault", [
    "zero_model", "signs_flipped", "imaginary_parts", "rectifier_dropped",
    "padded_to_784"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from keystone_tpu.pipelines.images import mnist_random_fft as app

    if fault == "zero_model":
        monkeypatch.setattr(mnist, "fit", zero_model(mnist.fit))
    else:
        change = {"signs_flipped": {"flip": True},
                  "imaginary_parts": {"table": sines},
                  "rectifier_dropped": {"thresh": -np.inf},
                  "padded_to_784": {"table": unpadded}}[fault]
        monkeypatch.setattr(app, "RandomFFTFeatures", faulty_bank(**change))
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is False, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_sound_run_is_correct():
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert all(np.isfinite(c["value"]) for c in result["compared"].values())
