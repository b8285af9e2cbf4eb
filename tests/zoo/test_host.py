"""ModelZoo hosting behavior: lazy page-in through the
build-outside-lock path (deduped under concurrency), CSE co-hosting,
LRU resident-set eviction with pinning, drain isolation between
models, plan overrides, and the /planz document."""

import threading

import numpy as np
import pytest

from keystone_tpu.observability.registry import MetricsRegistry
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.serving.featurize import build_featurize_pipeline
from keystone_tpu.zoo import (
    BuiltModel,
    ModelPlacement,
    ModelRegistry,
    ModelSpec,
    ModelZoo,
    PlacementPlan,
    UnknownModel,
)

D = 6
IMG = 8


def _head(seed):
    return build_pipeline(d=D, hidden=8, depth=2, seed=seed)


def _solo_spec(mid, seed, **kw):
    head = _head(seed)
    kw.setdefault("buckets", (2, 4))
    kw.setdefault("lanes", 1)
    kw.setdefault("max_delay_ms", 1.0)
    kw.setdefault("warmup_example", np.zeros(D, np.float32))
    return ModelSpec(
        model_id=mid,
        build=lambda: BuiltModel(fitted=head),
        **kw,
    ), head


def _zoo(specs, **kw):
    reg = ModelRegistry()
    for spec in specs:
        reg.register(spec)
    kw.setdefault("cse", False)
    kw.setdefault("metrics_registry", MetricsRegistry())
    return ModelZoo(reg, **kw)


def _solo_want(head, x, featurize=None):
    eng = CompiledPipeline(
        head, (2, 4), featurize=featurize,
        donate=False,
    )
    return np.asarray(eng.apply(np.asarray(x)[None], sync=True))[0]


def test_resolve_default_and_unknown():
    spec_a, _ = _solo_spec("alpha", 1, default=True)
    spec_b, _ = _solo_spec("beta", 2)
    zoo = _zoo([spec_a, spec_b])
    assert zoo.resolve(None)[0] == "alpha"
    assert zoo.resolve("beta")[0] == "beta"
    with pytest.raises(UnknownModel) as ei:
        zoo.resolve("nope")
    assert ei.value.registered == ("alpha", "beta")
    # nothing paged in by lookups alone
    assert zoo.planz()["actual"]["alpha"]["resident"] is False
    zoo.close()


def test_predict_routes_per_model():
    spec_a, head_a = _solo_spec("alpha", 1, default=True)
    spec_b, head_b = _solo_spec("beta", 2)
    with _zoo([spec_a, spec_b]) as zoo:
        x = np.linspace(-1, 1, D).astype(np.float32)
        got_a = np.asarray(zoo.predict(x, "alpha").result(timeout=60))
        got_b = np.asarray(zoo.predict(x, "beta").result(timeout=60))
        got_default = np.asarray(zoo.predict(x).result(timeout=60))
        np.testing.assert_allclose(
            got_a, _solo_want(head_a, x), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            got_b, _solo_want(head_b, x), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_array_equal(got_default, got_a)
        assert not np.allclose(got_a, got_b)


def test_cse_group_shares_one_gateway_with_parity():
    feat, feat_d = build_featurize_pipeline(img=IMG)
    heads = {
        "alpha": build_pipeline(d=feat_d, hidden=8, depth=2, seed=1),
        "beta": build_pipeline(d=feat_d, hidden=8, depth=2, seed=2),
    }

    def spec(mid, default=False):
        return ModelSpec(
            model_id=mid,
            build=lambda h=heads[mid]: BuiltModel(
                fitted=h, featurize=feat
            ),
            buckets=(2, 4),
            lanes=1,
            max_delay_ms=1.0,
            input_dtype=np.uint8,
            default=default,
        )

    with _zoo([spec("alpha", True), spec("beta")], cse=True) as zoo:
        hosted = zoo.host()
        assert ("alpha", "beta") in hosted
        # one unit, one gateway, one engine set for both models
        assert zoo.gateway_for("alpha") is zoo.gateway_for("beta")
        rng = np.random.default_rng(5)
        x = rng.integers(0, 256, (IMG, IMG, 3), dtype=np.uint8)
        for mid in heads:
            got = np.asarray(zoo.predict(x, mid).result(timeout=60))
            eng = CompiledPipeline(
                heads[mid], (2, 4), featurize=feat,
                donate=False,
            )
            want = np.asarray(eng.apply(x[None], sync=True))[0]
            np.testing.assert_allclose(
                got, want, rtol=1e-4, atol=1e-5
            )
        row = zoo.planz()["actual"]["alpha"]
        assert row["resident"] is True
        assert row["shared_with"] == ["beta"]


def test_predict_many_joins_across_units():
    spec_a, head_a = _solo_spec("alpha", 1, default=True)
    spec_b, head_b = _solo_spec("beta", 2)
    with _zoo([spec_a, spec_b]) as zoo:
        x = np.linspace(-1, 1, D).astype(np.float32)
        out = zoo.predict_many(x).result(timeout=60)
        assert sorted(out) == ["alpha", "beta"]
        np.testing.assert_allclose(
            np.asarray(out["alpha"]), _solo_want(head_a, x),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(out["beta"]), _solo_want(head_b, x),
            rtol=1e-4, atol=1e-5,
        )


def test_lru_eviction_respects_pinning():
    spec_keep, _ = _solo_spec("keep", 1, pinned=True, default=True)
    spec_b, _ = _solo_spec("bbb", 2)
    spec_c, _ = _solo_spec("ccc", 3)
    zoo = _zoo([spec_keep, spec_b, spec_c], max_resident=1)
    x = np.zeros(D, np.float32)
    try:
        zoo.predict(x, "keep").result(timeout=60)
        zoo.predict(x, "bbb").result(timeout=60)
        # keep is pinned: hosting bbb overflows the cap but never
        # evicts the pinned model
        actual = zoo.planz()["actual"]
        assert actual["keep"]["resident"] is True
        assert actual["bbb"]["resident"] is True
        zoo.predict(x, "ccc").result(timeout=60)
        actual = zoo.planz()["actual"]
        assert actual["keep"]["resident"] is True
        assert actual["bbb"]["resident"] is False  # the LRU victim
        assert actual["ccc"]["resident"] is True
        assert zoo._evictions_c.get(("bbb",)) == 1.0
        assert zoo._resident_g.get(("bbb",)) == 0.0
        # an evicted model pages back in on demand, same answers
        got = np.asarray(zoo.predict(x, "bbb").result(timeout=60))
        assert got.shape == (D,)
        assert zoo._pageins_c.get(("bbb",)) == 2.0
    finally:
        zoo.close()


def test_lru_order_is_by_last_use():
    spec_a, _ = _solo_spec("aaa", 1, default=True)
    spec_b, _ = _solo_spec("bbb", 2)
    spec_c, _ = _solo_spec("ccc", 3)
    zoo = _zoo([spec_a, spec_b, spec_c], max_resident=2)
    x = np.zeros(D, np.float32)
    try:
        zoo.predict(x, "aaa").result(timeout=60)
        zoo.predict(x, "bbb").result(timeout=60)
        zoo.predict(x, "aaa").result(timeout=60)  # refresh aaa
        zoo.predict(x, "ccc").result(timeout=60)
        actual = zoo.planz()["actual"]
        assert actual["bbb"]["resident"] is False  # least recent
        assert actual["aaa"]["resident"] is True
        assert actual["ccc"]["resident"] is True
    finally:
        zoo.close()


def test_concurrent_cold_predicts_page_in_once():
    spec, _ = _solo_spec("solo", 1, default=True)
    zoo = _zoo([spec])
    x = np.zeros(D, np.float32)
    outs, errors = [], []

    def client():
        try:
            outs.append(zoo.predict(x, "solo").result(timeout=60))
        except Exception as e:  # pragma: no cover - fails the test
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(outs) == 8
        # every concurrent cold request waited on ONE build instead
        # of compiling a duplicate generation
        assert zoo._pageins_c.get(("solo",)) == 1.0
    finally:
        zoo.close()


def test_evicting_one_model_never_stalls_another():
    spec_a, _ = _solo_spec("aaa", 1, default=True)
    spec_b, head_b = _solo_spec("bbb", 2)
    zoo = _zoo([spec_a, spec_b])
    x = np.zeros(D, np.float32)
    try:
        zoo.predict(x, "aaa").result(timeout=60)
        zoo.predict(x, "bbb").result(timeout=60)
        # eviction drains aaa on a background thread; bbb keeps
        # serving the whole time
        assert zoo.evict("aaa") is True
        got = np.asarray(zoo.predict(x, "bbb").result(timeout=60))
        np.testing.assert_allclose(
            got, _solo_want(head_b, x), rtol=1e-4, atol=1e-5
        )
        assert zoo.evict("aaa") is False  # already gone
    finally:
        zoo.close()


def test_plan_overrides_spec_shape():
    spec, _ = _solo_spec("mmm", 1, buckets=(2, 4), lanes=1,
                         default=True)
    plan = PlacementPlan(
        placements=(ModelPlacement(
            model_id="mmm", buckets=(1, 8), lanes=2, sharded=False,
            params_nbytes=0, demand_share=1.0,
            predicted_efficiency=None, reason="test",
        ),),
        lane_budget=2,
        hbm_budget_bytes=None,
    )
    with _zoo([spec], plan=plan) as zoo:
        gw = zoo.gateway_for("mmm")
        status = gw.pool.status()
        assert tuple(status["buckets"]) == (1, 8)
        assert status["lanes"] == 2
        doc = zoo.planz()
        assert doc["plan"]["placements"][0]["lanes"] == 2
        # spec shape still reported next to the plan's
        assert doc["actual"]["mmm"]["spec_lanes"] == 1


def test_closed_zoo_rejects_work():
    spec, _ = _solo_spec("solo", 1, default=True)
    zoo = _zoo([spec])
    zoo.predict(np.zeros(D, np.float32)).result(timeout=60)
    zoo.close()
    assert zoo.ready is False
    with pytest.raises(RuntimeError, match="closed"):
        zoo.predict(np.zeros(D, np.float32))


def _goodput(zoo):
    per = zoo.attribution.per_model()
    return {m: cell["goodput_rows"] for m, cell in per.items()}


def _engine_examples(zoo, mid):
    return sum(
        lane.engine.metrics.examples.total
        for lane in zoo.gateway_for(mid).pool.lanes
    )


def test_predict_many_shared_unit_accounts_each_model_once():
    """One ``predict_many`` over a co-hosted pair is ONE submit to the
    shared unit: the engine sees exactly one admitted row, and the
    ledger charges each member its even split of that single row —
    never a full row per member (double counting) and never zero."""
    feat, feat_d = build_featurize_pipeline(img=IMG)
    heads = {
        "alpha": build_pipeline(d=feat_d, hidden=8, depth=2, seed=1),
        "beta": build_pipeline(d=feat_d, hidden=8, depth=2, seed=2),
    }

    def spec(mid, default=False):
        return ModelSpec(
            model_id=mid,
            build=lambda h=heads[mid]: BuiltModel(
                fitted=h, featurize=feat
            ),
            buckets=(2, 4),
            lanes=1,
            max_delay_ms=1.0,
            input_dtype=np.uint8,
            default=default,
        )

    with _zoo([spec("alpha", True), spec("beta")], cse=True) as zoo:
        zoo.host()
        rng = np.random.default_rng(7)
        x = rng.integers(0, 256, (IMG, IMG, 3), dtype=np.uint8)
        zoo.predict_many(x).result(timeout=60)  # warm compile path
        rows0 = _engine_examples(zoo, "alpha")
        good0 = _goodput(zoo)
        out = zoo.predict_many(x).result(timeout=60)
        assert sorted(out) == ["alpha", "beta"]
        assert _engine_examples(zoo, "alpha") == rows0 + 1
        good = _goodput(zoo)
        assert good["alpha"] - good0.get("alpha", 0) == pytest.approx(0.5)
        assert good["beta"] - good0.get("beta", 0) == pytest.approx(0.5)
        # and the sum invariant survives: ledger total == engine total
        assert sum(good.values()) == pytest.approx(
            _engine_examples(zoo, "alpha")
        )


def test_predict_many_solo_units_account_each_model_once():
    """Across SOLO units the fan-out is one submit per unit: each
    model's engine admits one row and each model's ledger account is
    charged exactly one full row."""
    spec_a, _ = _solo_spec("alpha", 1, default=True)
    spec_b, _ = _solo_spec("beta", 2)
    with _zoo([spec_a, spec_b]) as zoo:
        x = np.linspace(-1, 1, D).astype(np.float32)
        zoo.predict_many(x).result(timeout=60)  # warm compile path
        rows0 = {m: _engine_examples(zoo, m) for m in ("alpha", "beta")}
        good0 = _goodput(zoo)
        zoo.predict_many(x).result(timeout=60)
        for mid in ("alpha", "beta"):
            assert _engine_examples(zoo, mid) == rows0[mid] + 1
            assert _goodput(zoo)[mid] - good0.get(mid, 0) == (
                pytest.approx(1.0)
            )


# -- attribution and drift through a hosted, planned zoo --------------------


def _planned_cse_zoo(base_mix):
    """Two heads on one featurize prefix (so the ledger's fair split is
    in play), hosted, and a plan applied whose profiles pin each model's
    expected request sizes as its drift baseline."""
    from keystone_tpu.zoo.optimizer import ChipBudget, plan_placement

    feat, feat_d = build_featurize_pipeline(img=IMG)
    heads = {
        "alpha": build_pipeline(d=feat_d, hidden=8, depth=2, seed=1),
        "beta": build_pipeline(d=feat_d, hidden=8, depth=2, seed=2),
    }
    specs = [
        ModelSpec(
            model_id=mid,
            build=lambda h=head: BuiltModel(fitted=h, featurize=feat),
            buckets=(2, 8, 32),
            lanes=1,
            max_delay_ms=1.0,
            input_dtype=np.uint8,
            warmup_example=np.zeros((IMG, IMG, 3), np.uint8),
            expected_sizes=dict(base_mix),
            default=(mid == "alpha"),
        )
        for mid, head in heads.items()
    ]
    zoo = _zoo(specs, cse=True)
    zoo.host()
    profiles = zoo.profiles(build=True)
    budget = ChipBudget(lane_budget=2)
    zoo.apply_plan(
        plan_placement(profiles, budget), budget=budget, profiles=profiles
    )
    return zoo


def _drive(zoo, schedule):
    rng = np.random.default_rng(23)
    x = rng.integers(0, 256, (IMG, IMG, 3), dtype=np.uint8)
    for mid, size in schedule:
        zoo.observe_request(mid, size)
        for f in [zoo.predict(x, mid) for _ in range(size)]:
            f.result(timeout=60)


def test_ledger_totals_equal_the_engines_in_every_field():
    """Per-model charges sum to what the engines counted — rows, padded
    rows, dispatches, modelled FLOPs, H2D bytes, device seconds — with
    both models' windows split on one shared engine."""
    with _planned_cse_zoo({1: 80, 2: 20}) as zoo:
        _drive(zoo, [("alpha", 1), ("beta", 2), ("alpha", 5), ("beta", 1)])
        engines = {
            "goodput_rows": 0.0, "padded_rows": 0.0, "dispatches": 0.0,
            "device_flops": 0.0, "h2d_bytes": 0.0, "device_seconds": 0.0,
        }
        gw = zoo.gateway_for("alpha")
        assert gw is zoo.gateway_for("beta")  # the CSE group's engines
        for lane in gw.pool.lanes:
            m = lane.engine.metrics
            engines["goodput_rows"] += m.examples.total
            engines["padded_rows"] += m.padded_rows.total
            engines["dispatches"] += m.dispatches.total
            engines["device_flops"] += m.device_flops.total
            engines["h2d_bytes"] += m.h2d_bytes.total
            engines["device_seconds"] += (
                m.dispatch_latency.snapshot()["total"]
            )
        ledger = zoo.attribution.totals()
        assert engines["goodput_rows"] == 9
        for field, total in engines.items():
            assert ledger[field] == pytest.approx(total, rel=1e-6), field
        doc = zoo.attributionz()
        assert set(doc["models"]) == {"alpha", "beta"}


def test_drift_flags_the_shifted_model_only_and_the_replan_follows_it():
    """Traffic that matches the plan's mixture flags nobody; when one
    model's requests move from singles to 24 rows, its PSI crosses the
    threshold and the other's does not, and ``driftz`` carries a re-plan
    (recommended, never applied) that changes the shifted model and
    covers its new size with a tighter bucket than the applied plan."""
    base = [1] * 16 + [2] * 4
    with _planned_cse_zoo({1: 80, 2: 20}) as zoo:
        before = {
            m: zoo.plan.placement_for(m).buckets for m in ("alpha", "beta")
        }
        _drive(zoo, [(m, s) for s in base for m in ("alpha", "beta")])
        assert zoo.driftz()["drifted"] == []
        assert zoo.driftz()["recommendation"] is None
        _drive(zoo, [("alpha", 24)] * 20 + [("beta", s) for s in base])
        doc = zoo.driftz()
        assert doc["drifted"] == ["alpha"]
        assert doc["scores"]["alpha"] > doc["threshold"]
        assert doc["scores"]["beta"] <= doc["threshold"]
        rec = doc["recommendation"]
        assert "alpha" in rec["changes"]
        proposed = {
            p["model"]: tuple(p["buckets"])
            for p in rec["proposed_plan"]["placements"]
        }

        def covering(buckets):
            fits = [b for b in buckets if b >= 24]
            return min(fits) if fits else max(buckets)

        assert covering(proposed["alpha"]) < covering(before["alpha"])
        # recommended only: the applied plan is what it was
        assert zoo.plan.placement_for("alpha").buckets == before["alpha"]
