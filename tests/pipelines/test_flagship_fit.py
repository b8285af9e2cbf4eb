"""The flagship's fit from Fisher vectors to model through
``imagenet_sift_lcs_fv.fit_classifier`` (what ``build_pipeline`` ends in
and what the benchmark's cell ``weighted-bcd-fit`` calls), on seeded
features from the configuration's own generator at a small size, against
the benchmark's plain float64 reference; and the solver's spans and
counters, once a fit."""

import dataclasses
import importlib.util
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.programs import flagship_solver
from benchmark.reference import imagenet_fv_weighted_bcd as reference
from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import disable_tracing, enable_tracing
from keystone_tpu.ops.learning.weighted_ls import (
    BlockWeightedLeastSquaresEstimator,
)
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as app
from keystone_tpu.workflow.api import Identity

from test_imagenet_sift_lcs_fv import _synthetic_imagenet


def _suite_translation():
    """tests/ops/test_weighted_ls.py's numpy translation of the
    reference's loop (another directory, so loaded by path)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ops", "test_weighted_ls.py")
    spec = importlib.util.spec_from_file_location("_weighted_ls_suite", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.ref_block_weighted_bcd


EMPTY = 3  # the class whose rows are given to its neighbour


def small_inputs(seed=5, rows=1536, empty=True):
    """The generator's inputs at the tiny configuration's sizes (1,024
    features, 20 classes of unequal size), one class emptied."""
    config = bench_run.load_json(
        bench_run.HERE, "tests", "tiny", "imagenet-fv-weighted-bcd.json")
    workload = {"traffic": {"rows_per_chip": rows, "heldout_rows": 64,
                            "check_classes": 6}}
    cell = {"name": "weighted-bcd-fit", "chips": 1}
    ctx = bench_run.Context(cell, config, workload, seed, 0.1, False)
    ctx.devices = jax.devices()[:1]
    inputs = flagship_solver.make_inputs(ctx)
    if empty:
        y = np.array(inputs["y"])
        y[y == EMPTY] = EMPTY + 1
        inputs["y"] = jax.numpy.asarray(y)
        inputs["labels"] = Dataset.from_array(inputs["y"])
    return ctx, inputs


@pytest.fixture(scope="module")
def fitted():
    ctx, inputs = small_inputs()
    model = flagship_solver.fit(inputs)
    out = flagship_solver.outputs(model, inputs)
    sample = flagship_solver.reference_inputs(inputs)
    return ctx, inputs, out, sample


def test_generator_gives_unit_rows_and_unequal_classes():
    _, inputs = small_inputs(empty=False)
    x, y = np.asarray(inputs["x"]), np.asarray(inputs["y"])
    assert x.dtype == np.float32 and x.shape == (1536, 1024)
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, atol=1e-5)
    counts = np.bincount(y, minlength=20)
    assert counts.min() > 0 and counts.min() < counts.max()
    assert counts.min() >= 0.5 * counts.max()
    # correlated columns: the covariance is far from a multiple of I
    cov = np.cov(x[:, :64].T)
    off = cov - np.diag(np.diag(cov))
    assert np.abs(off).max() > 0.05 * np.diag(cov).mean()


def test_same_seed_same_inputs_other_seed_other_inputs():
    _, a = small_inputs(seed=2 ** 31 + 7, rows=256, empty=False)
    _, b = small_inputs(seed=2 ** 31 + 7, rows=256, empty=False)
    _, c = small_inputs(seed=8, rows=256, empty=False)
    np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
    np.testing.assert_array_equal(np.asarray(a["y"]), np.asarray(b["y"]))
    assert not np.array_equal(np.asarray(a["x"]), np.asarray(c["x"]))


def test_fitted_model_matches_the_direct_float64_solution(fitted):
    ctx, _, out, sample = fitted
    cfg = ctx.config
    w, lam = float(cfg["mixture_weight"]), float(cfg["lambda"])
    m = reference.Moments(sample["x"], sample["y"], int(cfg["num_classes"]))
    assert m.counts[EMPTY] == 0 and len(set(m.counts)) > 3
    present = np.flatnonzero(m.counts > 0)
    want_w, want_b = reference.direct_solution(m, present, w, lam)
    assert reference.rel_err(out["W"][:, present], want_w) < 2e-4
    np.testing.assert_allclose(out["intercept"][present], want_b, atol=2e-5)
    want = sample["x_test"].astype(np.float64) @ want_w + want_b
    assert reference.rel_err(out["scores"][:, present], want) < 1e-4
    # a class without rows gets no model
    assert np.all(out["W"][:, EMPTY] == 0.0)


def test_compare_reads_the_fit_as_correct_and_skips_the_empty_class(fitted):
    ctx, _, out, sample = fitted
    got = reference.compare(ctx, dict(sample, outputs={"first": out}))
    assert got["scores_rel_err"] < 1e-4
    assert got["system_rel_residual"] < 3e-5
    p = reference.prepared(ctx, dict(sample, outputs={"first": out}))
    assert EMPTY not in p.present and EMPTY not in p.drawn
    assert len(p.drawn) == 6


def test_reference_agrees_with_the_suite_s_own_translation(fitted):
    """Two float64 references written apart: this PR's, from the class
    systems, and test_weighted_ls.py's, from the reference's loop."""
    ctx, _, _, sample = fitted
    cfg = ctx.config
    w, lam = float(cfg["mixture_weight"]), float(cfg["lambda"])
    x, y = sample["x"][:, :96], sample["y"]
    labels = 2.0 * np.eye(20)[y] - 1.0
    keep = [c for c in range(20) if c != EMPTY]
    # the translation divides by a class's count: leave the empty one out
    want_w, want_b = _suite_translation()(x, labels[:, keep], 96, 1, lam, w)
    m = reference.Moments(np.ascontiguousarray(x),
                          np.searchsorted(keep, y), len(keep))
    got_w, got_b = reference.direct_solution(m, range(len(keep)), w, lam)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(got_b, want_b, rtol=1e-7, atol=1e-9)


def solver_of(pipeline):
    found = [op for op in pipeline._graph.operators.values()
             if isinstance(op, BlockWeightedLeastSquaresEstimator)]
    assert len(found) == 1
    return found[0]


def test_build_pipeline_and_fit_classifier_build_the_same_solver_node():
    conf = app.ImageNetSiftLcsFVConfig(
        desc_dim=8, vocab_size=2, lam=1e-4, mixture_weight=0.25,
        num_classes=6, lcs_stride=8, num_pca_samples_per_image=20,
        num_gmm_samples_per_image=20)
    train = _synthetic_imagenet(n_per_class=2, num_classes=6, size=48)
    from keystone_tpu.loaders.image_loaders import (
        ImageExtractor, LabelExtractor)

    whole = app.build_pipeline(
        ImageExtractor.apply(train), LabelExtractor.apply(train), conf)
    features = Dataset.from_array(np.zeros((12, 64), np.float32))
    labels = Dataset.from_array(np.arange(12, dtype=np.int32) % 6)
    tail = app.fit_classifier(Identity(), features, labels, conf)
    a, b = solver_of(whole), solver_of(tail)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)
    # the reference's settings and the estimator's own defaults
    assert (a.block_size, a.num_iter, a.num_features) == (4096, 1, 64)
    assert (a.lam, a.mixture_weight) == (1e-4, 0.25)
    defaults = BlockWeightedLeastSquaresEstimator(4096, 1, 1e-4, 0.25)
    for option in ("solve", "layout", "convergence_check", "pcg_tol",
                   "class_chunk"):
        assert getattr(a, option) == getattr(defaults, option)
    # both end in the top-5
    for pipe in (whole, tail):
        assert any(type(op).__name__ == "TopKClassifier"
                   for op in pipe._graph.operators.values())


def counters():
    out = {}
    for family in get_global_registry().collect():
        if family.name.startswith("keystone_solver_wls_"):
            for s in family.samples:
                out[(family.name, tuple(sorted(s.labels.items())))] = s.value
    return out


@pytest.mark.parametrize("features,solve,layout", [
    (1024, "pcg", "sorted"), (1024, "pcg", "original"),
    (64, "chol", "grouped")])
def test_spans_and_counters_appear_once_a_fit_and_name_the_path(
        monkeypatch, features, solve, layout):
    if layout == "original":
        # no room for the class-sorted copy: the one-hot matvec runs
        from keystone_tpu.ops.learning import weighted_ls
        monkeypatch.setattr(weighted_ls, "_device_memory_limit", lambda: 1)
    rng = np.random.default_rng(3)
    n, c = 240, 5
    x = rng.standard_normal((n, features)).astype(np.float32)
    y = rng.integers(0, c, n)
    labels = (2.0 * np.eye(c)[y] - 1.0).astype(np.float32)
    est = BlockWeightedLeastSquaresEstimator(4096, 1, 1e-2, 0.25)
    before = counters()
    tracer = enable_tracing()
    try:
        tracer.clear()
        model = est.fit(Dataset.from_array(x), Dataset.from_array(labels))
        names = [s.name for s in tracer.recent()
                 if s.name.startswith("solver.wls.")]
    finally:
        disable_tracing()
    after = counters()
    delta = {k: after[k] - before.get(k, 0.0) for k in after
             if after[k] != before.get(k, 0.0)}
    path = ("keystone_solver_wls_path_total",
            (("layout", layout), ("solve", solve)))
    assert delta.pop(("keystone_solver_wls_fits_total", ())) == 1
    assert delta.pop(path) == 1
    if layout == "sorted":
        assert delta.pop(
            ("keystone_solver_wls_sorted_fits_total", ())) == 1
        assert delta.pop(
            ("keystone_solver_wls_sorted_stats_fits_total", ())) == 1
        # single-label ±1 indicators: the first step's moments from the
        # class sums
        assert delta.pop(
            ("keystone_solver_wls_label_moments_fits_total", ())) == 1
    if solve == "pcg":
        assert sorted(names) == ["solver.wls.converged",
                                 "solver.wls.dispatch", "solver.wls.layout",
                                 "solver.wls.prep"]
        iterations = int(model.solver_info["pcg_iterations"])
        assert 0 < iterations < 96
        assert delta.pop(
            ("keystone_solver_wls_pcg_iterations_total", ())) == iterations
    else:
        assert sorted(names) == ["solver.wls.dispatch", "solver.wls.prep"]
    assert delta == {}


def test_convergence_check_off_reads_nothing():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((128, 1024)).astype(np.float32)
    labels = (2.0 * np.eye(4)[rng.integers(0, 4, 128)] - 1.0).astype(
        np.float32)
    est = BlockWeightedLeastSquaresEstimator(
        4096, 1, 1e-2, 0.25, convergence_check="off")
    before = counters()
    tracer = enable_tracing()
    try:
        tracer.clear()
        est.fit(Dataset.from_array(x), Dataset.from_array(labels))
        names = {s.name for s in tracer.recent()}
    finally:
        disable_tracing()
    assert "solver.wls.converged" not in names
    key = ("keystone_solver_wls_pcg_iterations_total", ())
    assert counters().get(key, 0.0) == before.get(key, 0.0)
