"""``cifar-krr-fit``: the control reads above the tiny cell's limit, and
each fault planted in the program under the harness comes out with
``correct`` false, at a size a test run can hold (16 filters, 24 images x
10 crops, four blocks of 60: tests/tiny/cifar-krr-fit.json, whose limit is
the tiny cell's own; the full-size one was set on the chip, PERF.md
section 2). The program's three bf16 passes cannot be told from float32 on
a CPU, whose products are float32 whatever is asked for; one pass of
operands rounded to bf16 can be planted, and is."""

import dataclasses

import numpy as np
import pytest

from benchmark import run
from benchmark.programs import cifar_krr
from benchmark.tests.test_controls import drive
from benchmark.tests.test_dry_run import MANIFEST, tiny

CELL = "cifar-krr-fit"


@pytest.fixture(scope="module")
def driven():
    ctx, workload, reference, sample = drive(CELL)
    return workload, reference.compare(ctx, sample), \
        reference.control(ctx, sample)


def test_sound_fit_reads_under_the_limit(driven):
    workload, sound, _ = driven
    assert sound["scores_rel_err"] <= \
        workload["limits"]["scores_rel_err"] / 1.5, sound


def test_control_comes_out_not_correct(driven):
    workload, _, low = driven
    assert low["bfloat16_cross_term"]["scores_rel_err"] > \
        1.5 * workload["limits"]["scores_rel_err"], low


def with_conf(**changes):
    """The application built from a changed configuration."""
    def plant(build):
        def broken(inputs):
            return build(dict(inputs, conf=dataclasses.replace(
                inputs["conf"], **changes)))
        return broken
    return plant


def block_order_reversed(build):
    def broken(inputs):
        from keystone_tpu.ops.learning.kernel import KernelRidgeRegression

        original = KernelRidgeRegression._epoch_order
        KernelRidgeRegression._epoch_order = \
            lambda self, epoch, n: original(self, epoch, n)[::-1]
        try:
            return build(inputs).fit()
        finally:
            KernelRidgeRegression._epoch_order = original
    return broken


def scaler_left_out(build):
    def broken(inputs):
        from keystone_tpu.ops.stats import nodes
        from keystone_tpu.pipelines.images import cifar_apps as app

        original = app.StandardScaler
        try:
            app.StandardScaler = lambda: nodes.StandardScaler(
                normalize_std_dev=False)
            return build(inputs)
        finally:
            app.StandardScaler = original
    return broken


def one_bf16_pass(build, outputs=cifar_krr.outputs):
    """The kernel's cross term at one pass of operands rounded to bf16,
    in the fit and in the fitted model alike."""
    def broken(inputs):
        import jax
        import jax.numpy as jnp

        from keystone_tpu.ops.learning import kernel

        def low(a, b):
            return jax.lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        original = kernel._cross_mm_x3
        programs = (kernel._krr_epoch_scan, kernel._rbf_cross_block)
        kernel._cross_mm_x3 = low
        for program in programs:
            program.clear_cache()
        try:
            fitted = build(inputs).fit()
            return fitted, outputs(fitted, inputs)
        finally:
            kernel._cross_mm_x3 = original
            for program in programs:
                program.clear_cache()
    return broken


def zero_model(fit):
    def broken(inputs):
        fitted = fit(inputs)
        cifar_krr._model(fitted).model = cifar_krr._model(fitted).model * 0.0
        return fitted
    return broken


def run_broken(monkeypatch, name, plant):
    monkeypatch.setattr(cifar_krr, name, plant(getattr(cifar_krr, name)))
    cell, config, workload = tiny(CELL)
    return run.run_cell(MANIFEST, cell, config, workload, seed=13,
                        seconds=0.2, trace=False, require_chip=False)


@pytest.mark.parametrize("name,plant", [
    ("fit", zero_model),
    ("build", with_conf(gamma=1.0)),  # gamma dropped: exp(-|x - y|^2)
    ("build", with_conf(flip_chance=0.0)),  # crops not flipped
    ("build", scaler_left_out),
], ids=["zero_model", "gamma_dropped", "crops_not_flipped",
        "scaler_left_out"])
def test_planted_fault_is_not_correct(name, plant, monkeypatch):
    result = run_broken(monkeypatch, name, plant)
    assert result["correct"] is False, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


def fit_through(planted):
    """``cifar_krr.fit`` around a build that fits under its fault."""
    def fit(inputs):
        import jax

        from keystone_tpu.workflow.executor import PipelineEnv

        PipelineEnv.get_or_create().reset()
        fitted = planted(cifar_krr.build)(inputs)
        if isinstance(fitted, tuple):
            fitted, scores = fitted
            fitted.planted_scores = scores
        jax.block_until_ready(cifar_krr._model(fitted).model)
        return fitted
    return fit


def test_block_order_reversed_is_not_correct(monkeypatch):
    result = run_broken(
        monkeypatch, "fit", lambda _: fit_through(block_order_reversed))
    assert result["correct"] is False, result["compared"]


def test_cross_term_at_one_bf16_pass_is_not_correct(monkeypatch):
    """Planted in the program's own product; the scores are taken under
    the fault too (the fitted model's kernel rows use the same product)."""
    monkeypatch.setattr(
        cifar_krr, "outputs", lambda fitted, inputs: fitted.planted_scores)
    result = run_broken(
        monkeypatch, "fit", lambda _: fit_through(one_bf16_pass))
    assert result["correct"] is False, result["compared"]
    value = result["compared"]["scores_rel_err"]["value"]
    assert np.isfinite(value) and value < 0.05  # a precision fault, no more


def test_sound_run_is_correct():
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert np.isfinite(result["compared"]["scores_rel_err"]["value"])
