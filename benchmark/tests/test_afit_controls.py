"""``amazon-logreg-fit`` at a size a test run can hold (3,000 rows of
5,000 features: tests/tiny/amazon-logreg-fit.json, whose limits are the
tiny cell's own; the full-size ones were set on the chip, PERF.md
section 2): the adapter, the reference and ``flops_amazon`` dry-run on
the CPU; the control reads above the gradient's limit; each fault
planted in the program comes out with ``correct`` false."""

import json
import os

import numpy as np
import pytest

from benchmark import flops, flops_amazon, run
from benchmark.programs import amazon
from benchmark.tests.test_controls import drive
from benchmark.tests.test_dry_run import MANIFEST, tiny

CELL = "amazon-logreg-fit"
CFG = json.load(open(os.path.join(
    run.HERE, "configs", "amazon-reviews-logistic.json")))
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.fixture(autouse=True, scope="module")
def quick_kernel():
    """A narrower bucket and window unroll than the chip's: the Pallas
    interpreter runs the kernel's unrolled loops op by op, and the
    products do not depend on either."""
    from keystone_tpu.ops.learning import sparse_buckets

    chip = sparse_buckets.BUCKET, sparse_buckets.UNROLL
    sparse_buckets.BUCKET, sparse_buckets.UNROLL = 128, 2
    yield
    sparse_buckets.BUCKET, sparse_buckets.UNROLL = chip


@pytest.fixture(scope="module")
def driven():
    ctx, workload, reference, sample = drive(CELL)
    return ctx, workload, reference.compare(ctx, sample), \
        reference.control(ctx, sample), sample


def test_rows_are_the_vectorizers(driven):
    """Row ids in order, distinct sorted columns within a row, counts
    within the clip, values 1.0, the positive share as asked."""
    ctx, _, _, _, sample = driven
    idx, vals = sample["indices"], sample["values"]
    r, c = idx[:, 0], idx[:, 1]
    assert (np.diff(r) >= 0).all()
    same = r[1:] == r[:-1]
    assert (c[1:][same] > c[:-1][same]).all()
    assert c.min() >= 0 and c.max() < int(ctx.config["num_features"])
    counts = np.bincount(r, minlength=sample["rows"])
    lo, hi = ctx.traffic["nnz_clip"]
    assert counts.min() >= lo and counts.max() <= hi
    assert (vals == 1.0).all()
    assert abs(np.mean(sample["y"]) - 0.78) < 0.01


def test_sound_fit_reads_under_the_limits(driven):
    _, workload, sound, _, _ = driven
    for name, limit in workload["limits"].items():
        assert sound[name] <= limit / 1.5, sound


@pytest.mark.parametrize("departure,measure", [
    ("bfloat16_residuals", "grad_backward_err"),
    ("bfloat16_weights", "scores_rel_err")])
def test_control_comes_out_not_correct(driven, departure, measure):
    """The gradient's and the scores' controls fail their limits."""
    _, workload, _, low, _ = driven
    assert low[departure][measure] > 1.5 * workload["limits"][measure], low


def stopped_early(fit):
    def broken(inputs):
        est = amazon.estimator(inputs["config"])
        est.num_iters = 1
        from keystone_tpu.parallel.dataset import Dataset

        return est.fit(Dataset.from_array(inputs["x"], n=inputs["rows"]),
                       Dataset.from_array(inputs["y"]))
    return broken


def rounded_gradient(vg):
    """The program's gradient at one bf16 rounding."""
    import jax.numpy as jnp

    def broken(W, layout, onehot, mask, n, reg):
        f, g = vg(W, layout, onehot, mask, n, reg)
        return f, g.astype(jnp.bfloat16).astype(jnp.float32)
    return broken


def rounded_tables(product):
    """The bucket kernel reading W's columns and the residuals at one
    bf16 rounding: the program's products a precision step down."""
    import jax.numpy as jnp

    def broken(layout, table, n_dst, **kw):
        table = table.astype(jnp.bfloat16).astype(jnp.float32)
        return product(layout, table, n_dst, **kw)
    return broken


@pytest.mark.parametrize("fault", [
    "zero_model", "stopped_early", "gradient_rounded", "classes_swapped",
    "tables_rounded"])
def test_planted_fault_is_not_correct(fault, monkeypatch, request):
    from keystone_tpu.ops.learning import classifiers, sparse_buckets

    if fault == "zero_model":
        fit = amazon.fit

        def zero(inputs):
            m = fit(inputs)
            m.W = m.W * 0.0
            return m
        monkeypatch.setattr(amazon, "fit", zero)
    elif fault == "stopped_early":
        monkeypatch.setattr(amazon, "fit", stopped_early(amazon.fit))
    elif fault == "tables_rounded":
        import jax

        monkeypatch.setattr(sparse_buckets, "bucket_product",
                            rounded_tables(sparse_buckets.bucket_product))
        jax.clear_caches()  # the fit's programs, traced with the kernel
        request.addfinalizer(jax.clear_caches)
    elif fault == "gradient_rounded":
        monkeypatch.setattr(classifiers, "_jit_sparse_logistic_vg",
                            rounded_gradient(
                                classifiers._jit_sparse_logistic_vg))
    else:
        fit = amazon.fit

        def swapped(inputs):
            m = fit(inputs)
            m.W = m.W[:, ::-1]
            return m
        monkeypatch.setattr(amazon, "fit", swapped)
    cell, config, workload = tiny(CELL)
    result = run.run_cell(MANIFEST, cell, config, workload, seed=13,
                          seconds=0.2, trace=False, require_chip=False)
    assert result["correct"] is False, result["compared"]


def test_flops_by_hand():
    """One evaluation on 4,062,500 rows of 358M non-zeros: 4 operations
    and 16 bytes a non-zero (k − 1 = 1 product a direction), and the
    tables; bytes bind, at ~7 ms an evaluation."""
    nnz, n = 357_500_000, 4_062_500
    assert flops_amazon.sparse_grad(CFG, n, 1.0, nnz) == 4.0 * nnz
    tables = 4 * (100_000 * 2 + 2 * n)
    assert flops_amazon.sparse_grad_bytes(CFG, n, 1.0, nnz) \
        == 16.0 * nnz + tables
    least = flops.roofline_s(flops_amazon.sparse_grad(CFG, n, 1.0, nnz),
                             flops_amazon.sparse_grad_bytes(CFG, n, 1.0, nnz),
                             PEAKS)
    assert abs(least - 7.04e-3) < 5e-5
    assert flops_amazon.amazon_fit(CFG, n, 25.0, nnz) \
        == 25 * flops_amazon.sparse_grad(CFG, n, 1.0, nnz)
