"""``cifar-fit``: both controls read above the tiny cell's limit, and
each fault planted in the program under the harness comes out with
``correct`` false, at a size a test run can hold (32 filters, 192 images,
blocks of 64, pooling 16 wide so that a window is cut at the map's edge:
tests/tiny/cifar-fit.json, whose limit is the tiny cell's own; the
full-size one was set on the chip, PERF.md section 2). Products at three
bf16 passes cannot be planted in the program on a CPU, whose products
are float32 whatever precision is asked for: the reference's ``high``
control, written out pass by pass, stands for them."""

import numpy as np
import pytest

from benchmark import run
from benchmark.programs import cifar
from benchmark.tests.test_controls import drive
from benchmark.tests.test_dry_run import MANIFEST, tiny

CELL = "cifar-fit"


@pytest.fixture(scope="module")
def driven():
    ctx, workload, reference, sample = drive(CELL)
    return workload, reference.compare(ctx, sample), \
        reference.control(ctx, sample)


def test_sound_fit_reads_under_the_limit(driven):
    workload, sound, _ = driven
    limit = workload["limits"]["scores_rel_err"]
    assert sound["scores_rel_err"] <= limit / 1.5, sound


@pytest.mark.parametrize("departure", ["high", "bfloat16_features"])
def test_control_comes_out_not_correct(driven, departure):
    workload, _, low = driven
    limit = workload["limits"]["scores_rel_err"]
    assert low[departure]["scores_rel_err"] > 1.5 * limit, low


def swap_node(build, old_type, new_node):
    """The application's pipeline with one featurizer node replaced."""
    def broken(inputs):
        from keystone_tpu.ops.images import core
        from keystone_tpu.pipelines.images import random_patch_cifar as app

        original = getattr(app, old_type)
        try:
            setattr(app, old_type, new_node(getattr(core, old_type)))
            return build(inputs)
        finally:
            setattr(app, old_type, original)
    return broken


def alpha_dropped(build):
    return swap_node(build, "SymmetricRectifier",
                     lambda cls: lambda alpha: cls(alpha=0.0))


def windows_not_truncated(build):
    """A pooler whose last window keeps its full size by starting
    earlier (the tiny cell pools 16 wide at stride 13, so the second
    window is cut at the map's edge: 13..27 for 13..29)."""
    def shifted(cls):
        def make(stride, pool_size):
            import jax.numpy as jnp

            from keystone_tpu.workflow.api import Transformer

            class Full(Transformer):
                def apply_batch(self, ds):
                    x = ds.padded()
                    half, dim = pool_size // 2, x.shape[1]
                    starts = [min(c - half, dim - 2 * half)
                              for c in range(half, dim, stride)]
                    out = jnp.stack([jnp.stack([
                        x[:, a:a + 2 * half, b:b + 2 * half].sum(axis=(1, 2))
                        for b in starts], axis=1) for a in starts], axis=1)
                    return type(ds).from_array(out, n=ds.n)
            return Full()
        return make
    return swap_node(build, "Pooler", shifted)


def scaler_left_out(build):
    def broken(inputs):
        from keystone_tpu.ops.stats import nodes
        from keystone_tpu.pipelines.images import random_patch_cifar as app

        original = app.StandardScaler
        try:
            app.StandardScaler = lambda: nodes.StandardScaler(
                normalize_std_dev=False)
            return build(inputs)
        finally:
            app.StandardScaler = original
    return broken


def zero_model(fit):
    def broken(inputs):
        fitted = fit(inputs)
        cifar._model(fitted).W = cifar._model(fitted).W * 0.0
        return fitted
    return broken


@pytest.mark.parametrize("name,plant", [
    ("build", alpha_dropped), ("build", windows_not_truncated),
    ("build", scaler_left_out), ("fit", zero_model)])
def test_planted_fault_is_not_correct(name, plant, monkeypatch):
    monkeypatch.setattr(cifar, name, plant(getattr(cifar, name)))
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is False, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_features_in_bfloat16_are_not_correct(monkeypatch):
    """The program's features rounded to bfloat16 before the scaler."""
    def rounded(build):
        def broken(inputs):
            import jax.numpy as jnp

            from keystone_tpu.ops.images import core

            original = core._vectorize

            def low(arrays, x):
                out = original(arrays, x)
                return out.astype(jnp.bfloat16).astype(jnp.float32)

            core._vectorize = low
            try:
                return build(inputs).fit()
            finally:
                core._vectorize = original
        return broken

    fit = cifar.fit

    def broken_fit(inputs):
        import jax

        from keystone_tpu.workflow.executor import PipelineEnv

        PipelineEnv.get_or_create().reset()
        fitted = rounded(cifar.build)(inputs)
        jax.block_until_ready(cifar._model(fitted).W)
        return fitted

    assert fit is not broken_fit
    monkeypatch.setattr(cifar, "fit", broken_fit)
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is False, result["compared"]


def test_sound_run_is_correct():
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert np.isfinite(result["compared"]["scores_rel_err"]["value"])
