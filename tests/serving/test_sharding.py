"""serving/sharding.py: the declarative model-sharding layer — named
param extraction, rule matching, shard/gather placement, the
ParamBinder functionalization seam, and the model-sharded engine end
to end (parity vs replicated, compile bound, MFU device accounting).
Runs on the conftest's 8 virtual CPU devices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.serving import sharding
from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.workflow.api import Transformer

D = 32


@pytest.fixture(scope="module")
def fitted():
    # depth-2 square model: params = {W (32,32), b (32,)} x2
    return build_pipeline(d=D, hidden=D, depth=2)


@pytest.fixture
def mesh18():
    """(data=1, model=8): the pure model-sharding mesh."""
    m = mesh_lib.make_mesh(n_data=1, n_model=8)
    with mesh_lib.use_mesh(m):
        yield m


def batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, D)).astype(np.float32)


# -- named params ----------------------------------------------------------

def test_named_params_names_and_values(fitted):
    params = sharding.named_params(fitted)
    assert sorted(params) == [
        "0/_Affine/W", "0/_Affine/b", "1/_Affine/W", "1/_Affine/b",
    ]
    assert np.shape(params["0/_Affine/W"]) == (D, D)
    assert np.shape(params["1/_Affine/b"]) == (D,)
    # structurally identical pipeline built separately: SAME names
    # (topo position keys the namespace, not node ids)
    assert sorted(sharding.named_params(build_pipeline(
        d=D, hidden=D, depth=2
    ))) == sorted(params)


def test_named_params_skips_non_arrays_and_private():
    @dataclasses.dataclass(eq=False)
    class WithExtras(Transformer):
        W: object
        config: dict = dataclasses.field(default_factory=dict)
        scale: float = 2.0

        def apply(self, x):
            return x @ self.W * self.scale

    fitted = WithExtras(
        jnp.eye(3, dtype=jnp.float32), {"k": np.ones(3)}
    ).to_pipeline().fit()
    params = sharding.named_params(fitted)
    # the dict (even though it holds an array) and the float stay
    # baked constants; only the direct array field is a named param
    assert list(params) == ["0/WithExtras/W"]


# -- rule matching ---------------------------------------------------------

def test_match_first_rule_wins_and_scalars_replicate():
    params = {
        "0/Op/W": np.ones((8, 8), np.float32),
        "0/Op/scale": np.float32(3.0),          # scalar
        "0/Op/one": np.ones((1,), np.float32),  # one element
    }
    specs = sharding.match_partition_rules(
        (
            (r"/W$", PS(None, "model")),
            (r"/W$", PS("model", None)),  # shadowed: first match wins
            (r".*", PS()),
        ),
        params,
    )
    assert specs["0/Op/W"] == PS(None, "model")
    assert specs["0/Op/scale"] == PS()
    assert specs["0/Op/one"] == PS()


def test_match_unmatched_raises_by_name_or_replicates():
    params = {"0/Op/W": np.ones((4, 4), np.float32)}
    with pytest.raises(ValueError, match="0/Op/W"):
        sharding.match_partition_rules((), params)
    specs = sharding.match_partition_rules(
        (), params, unmatched="replicate"
    )
    assert specs["0/Op/W"] == PS()
    with pytest.raises(ValueError, match="unmatched"):
        sharding.match_partition_rules((), params, unmatched="bogus")


def test_default_rules_split_weights_replicate_biases(fitted):
    specs = sharding.match_partition_rules(
        sharding.DEFAULT_RULES, sharding.named_params(fitted)
    )
    assert specs["0/_Affine/W"] == PS(None, mesh_lib.MODEL_AXIS)
    assert specs["1/_Affine/W"] == PS(None, mesh_lib.MODEL_AXIS)
    assert specs["0/_Affine/b"] == PS()
    assert specs["1/_Affine/b"] == PS()


def test_resolve_param_sharding_dict_validates_names(fitted):
    resolved = sharding.resolve_param_sharding(
        {"0/_Affine/W": PS(None, "model")}, fitted
    )
    # named params not in the dict default to replicated
    assert resolved["1/_Affine/W"] == PS()
    with pytest.raises(ValueError, match="nope"):
        sharding.resolve_param_sharding({"nope": PS()}, fitted)


# -- placement -------------------------------------------------------------

@pytest.mark.needs_mesh8
def test_shard_and_gather_roundtrip(mesh18):
    W = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    specs = {"w": PS(None, "model")}
    shard_fns = sharding.make_shard_fns(specs, mesh18)
    placed = shard_fns["w"](W)
    assert len(placed.addressable_shards) == 8
    assert placed.addressable_shards[0].data.shape == (16, 1)
    gathered = sharding.make_gather_fns(specs, mesh18)["w"](placed)
    np.testing.assert_array_equal(np.asarray(gathered), W)


@pytest.mark.needs_mesh8
def test_shard_fn_rejects_uneven_split_by_name(mesh18):
    fns = sharding.make_shard_fns({"w": PS(None, "model")}, mesh18)
    with pytest.raises(ValueError, match="w dim 1"):
        fns["w"](np.ones((4, 6), np.float32))  # 6 % 8 != 0


@pytest.mark.needs_mesh8
def test_shard_fn_rejects_unknown_axis_and_long_spec(mesh18):
    fns = sharding.make_shard_fns({"w": PS("bogus",)}, mesh18)
    with pytest.raises(ValueError, match="bogus"):
        fns["w"](np.ones((8,), np.float32))
    fns = sharding.make_shard_fns({"w": PS(None, None, "model")}, mesh18)
    with pytest.raises(ValueError, match="more entries"):
        fns["w"](np.ones((8, 8), np.float32))


@pytest.mark.needs_mesh8
def test_placed_shard_bytes_and_params_nbytes(mesh18, fitted):
    params = sharding.named_params(fitted)
    total = sharding.params_nbytes(params)
    assert total == 2 * (D * D + D) * 4
    specs = sharding.match_partition_rules(
        sharding.DEFAULT_RULES, params
    )
    fns = sharding.make_shard_fns(specs, mesh18)
    placed = {k: fns[k](v) for k, v in params.items()}
    per_dev = sharding.placed_shard_bytes(placed)
    assert len(per_dev) == 8
    # each device: 1/8 of each W + the full (replicated) biases
    want = 2 * (D * D // 8) * 4 + 2 * D * 4
    assert set(per_dev.values()) == {want}
    assert max(per_dev.values()) < total


# -- the ParamBinder functionalization seam --------------------------------

def test_param_binder_substitutes_and_restores(fitted):
    binder = sharding.ParamBinder(fitted)
    x = batch(4)
    want = np.asarray(fitted._batch_run(jnp.asarray(x)))
    got = np.asarray(jax.jit(binder.run)(binder.params, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    # substituted params are LIVE arguments, not baked constants:
    # zeroed weights change the answer through the same traced fn
    zeroed = {
        k: np.zeros_like(np.asarray(v)) for k, v in binder.params.items()
    }
    out0 = np.asarray(jax.jit(binder.run)(zeroed, jnp.asarray(x)))
    assert not np.allclose(out0, want)
    np.testing.assert_allclose(out0, 0.0, atol=1e-7)  # tanh(0)=0

    # after tracing, the binder's private copy holds the pristine
    # values again (no tracer leaked into a field) and the CALLER's
    # pipeline was never touched
    for i, nid in enumerate(binder._pipeline._topo):
        op = binder._pipeline.graph.operators[nid]
        orig = fitted.graph.operators[fitted._topo[i]]
        np.testing.assert_array_equal(
            np.asarray(op.W), np.asarray(orig.W)
        )


def test_param_binder_on_already_used_pipeline():
    """Regression: a pipeline that already RAN carries lazily-attached
    per-operator jit caches (``_vmapped_apply``) closed over the
    ORIGINAL operators — a shallow copy that kept them would silently
    skip substitution and serve the baked weights. The binder scrubs
    the copies, so substitution works on a warm pipeline too."""
    fitted = build_pipeline(d=8, hidden=8, depth=2)
    x = batch(3)[:, :8].copy()
    fitted._batch_run(jnp.asarray(x))  # populate the op caches
    binder = sharding.ParamBinder(fitted)
    zeroed = {
        k: np.zeros_like(np.asarray(v)) for k, v in binder.params.items()
    }
    out0 = np.asarray(jax.jit(binder.run)(zeroed, jnp.asarray(x)))
    np.testing.assert_allclose(out0, 0.0, atol=1e-7)


# -- the model-sharded engine end to end -----------------------------------

@pytest.mark.needs_mesh8
def test_model_sharded_engine_matches_replicated(fitted, mesh18):
    plain = CompiledPipeline(fitted, buckets=(4, 8), name="shd-plain")
    engine = CompiledPipeline(
        fitted, buckets=(4, 8), name="shd-model", param_sharding=True
    )
    assert engine.model_sharded and engine.mesh is mesh18
    # params placed sharded: more than one shard per weight matrix
    placed_w = engine._placed_params["0/_Affine/W"]
    assert len(placed_w.addressable_shards) == 8
    for n in (1, 3, 4, 7, 8, 11):
        x = batch(n, seed=n)
        np.testing.assert_allclose(
            np.asarray(engine.apply(x, sync=True)),
            np.asarray(plain.apply(x, sync=True)),
            rtol=1e-5, atol=1e-6,
        )
    # the compile bound holds for GSPMD programs too
    assert engine.metrics.compile_count == 2


@pytest.mark.needs_mesh8
def test_model_sharded_composes_with_batch_sharding(fitted):
    """Rows over data, weights over model — one 2-D mesh."""
    m = mesh_lib.make_mesh(n_data=2, n_model=4)
    with mesh_lib.use_mesh(m):
        engine = CompiledPipeline(
            fitted, buckets=(4, 8), name="shd-2d",
            shard=True, param_sharding=True,
        )
    assert engine.buckets == (4, 8)  # 2 data shards divide both
    plain = CompiledPipeline(fitted, buckets=(4, 8), name="shd-2d-p")
    x = batch(7, seed=7)
    np.testing.assert_allclose(
        np.asarray(engine.apply(x, sync=True)),
        np.asarray(plain.apply(x, sync=True)),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.needs_mesh8
def test_model_sharded_composes_with_device_featurize(mesh18):
    """The fused featurize∘model program with the MODEL's params
    sharded: the featurize stage stays baked/replicated, the model
    weights ride as sharded arguments, outputs match the replicated
    fused engine."""
    from keystone_tpu.serving.featurize import build_featurize_pipeline

    feat, feat_d = build_featurize_pipeline(img=8)
    model = build_pipeline(d=feat_d, hidden=64, depth=2)
    raw = np.random.default_rng(5).integers(
        0, 256, (3, 8, 8, 3), dtype=np.uint8
    )
    plain = CompiledPipeline(
        model, buckets=(4,), featurize=feat, name="shd-fz-p"
    )
    shd = CompiledPipeline(
        model, buckets=(4,), featurize=feat, name="shd-fz-s",
        param_sharding=True,
    )
    np.testing.assert_allclose(
        np.asarray(shd.apply(raw, sync=True)),
        np.asarray(plain.apply(raw, sync=True)),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.needs_mesh8
def test_model_sharded_rounds_buckets_to_data_shards(fitted):
    """Regression: a model-sharded engine on a mesh with a >1 data
    axis mesh-places its staged batches, so buckets must round up to
    the data-shard count exactly as under ``shard=`` — an unrounded
    bucket failed every dispatch's device_put with a divisibility
    error."""
    m = mesh_lib.make_mesh()  # the DEFAULT mesh: data=8, model=1
    with mesh_lib.use_mesh(m):
        engine = CompiledPipeline(
            fitted, buckets=(2, 12), name="shd-round",
            param_sharding=True,
        )
    assert engine.buckets == (8, 16)
    plain = CompiledPipeline(fitted, buckets=(2, 12), name="shd-round-p")
    x = batch(3, seed=3)
    np.testing.assert_allclose(
        np.asarray(engine.apply(x, sync=True)),
        np.asarray(plain.apply(x, sync=True)),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.needs_mesh8
def test_model_sharded_warmup_then_no_new_compiles(fitted, mesh18):
    engine = CompiledPipeline(
        fitted, buckets=(4, 8), name="shd-warm", param_sharding=True,
    )
    engine.warmup(example=jnp.zeros((D,), jnp.float32))
    before = engine.metrics.compile_count
    assert before == 2
    for n in (1, 4, 6, 8):
        engine.apply(batch(n, seed=n), sync=True)
    assert engine.metrics.compile_count == before


@pytest.mark.needs_mesh8
def test_unmatched_param_fails_engine_construction_by_default(mesh18):
    @dataclasses.dataclass(eq=False)
    class Odd(Transformer):
        weird: object

        def apply(self, x):
            return x + self.weird

    fitted = Odd(
        jnp.ones((D,), jnp.float32)
    ).to_pipeline().fit()
    with pytest.raises(ValueError, match="0/Odd/weird"):
        CompiledPipeline(
            fitted, buckets=(4,), name="shd-odd",
            param_sharding=((r"/W$", PS(None, "model")),),
        )
    # the explicit flag downgrades to replication
    eng = CompiledPipeline(
        fitted, buckets=(4,), name="shd-odd2",
        param_sharding=((r"/W$", PS(None, "model")),),
        param_sharding_unmatched="replicate",
    )
    assert eng.param_sharding["0/Odd/weird"] == PS()


# -- MFU / device accounting (the audit satellite) -------------------------

@pytest.fixture
def pinned_peak(monkeypatch):
    from keystone_tpu.observability import device as device_obs

    monkeypatch.setenv("KEYSTONE_PEAK_FLOPS", "1e9")
    device_obs.reset_device_table()
    yield 1e9
    # drop the table derived under the pinned env so later tests
    # re-derive real peaks (monkeypatch restores the env afterwards)
    device_obs.reset_device_table()


@pytest.mark.needs_mesh8
def test_mfu_denominator_counts_mesh_devices_once(
    fitted, mesh18, pinned_peak
):
    """The regression pin for the accounting audit: a model-sharded
    engine's MFU denominator is peak x MESH devices (8) — counted from
    the mesh, exactly once — while a replicated engine's stays peak x
    1. Pinned via KEYSTONE_PEAK_FLOPS so the denominator is a known
    number, with an injectable clock so the windowed rate divides by
    a statement, not a wall clock."""
    from keystone_tpu.serving.metrics import ServingMetrics

    now = [0.0]
    sharded = CompiledPipeline(
        fitted, buckets=(8,), name="mfu-shd", param_sharding=True,
        metrics=ServingMetrics(clock=lambda: now[0]),
    )
    plain = CompiledPipeline(
        fitted, buckets=(8,), name="mfu-plain",
        metrics=ServingMetrics(clock=lambda: now[0]),
    )
    assert sharded.metrics._n_devices == 8
    assert plain.metrics._n_devices == 1
    sharded.warmup(example=jnp.zeros((D,), jnp.float32))
    plain.warmup(example=jnp.zeros((D,), jnp.float32))
    if not sharded.metrics.cost_models or not plain.metrics.cost_models:
        pytest.skip("backend reports no XLA cost analysis")
    sharded.apply(batch(8), sync=True)
    plain.apply(batch(8), sync=True)
    now[0] = 10.0
    for eng, n_dev in ((sharded, 8), (plain, 1)):
        mfu = eng.metrics.mfu()
        fps = eng.metrics.flops_per_sec()
        assert mfu is not None and fps > 0
        assert mfu == pytest.approx(fps / (pinned_peak * n_dev))


@pytest.mark.needs_mesh8
def test_two_sharded_lanes_each_count_the_mesh_not_lanes_x_mesh(
    fitted, mesh18, pinned_peak
):
    """N lanes sharing one mesh: each lane's engine runs on the SAME 8
    devices, so each denominator is 8 — never 8 x n_lanes."""
    from keystone_tpu.gateway import Gateway

    gw = Gateway(
        fitted, buckets=(4, 8), n_lanes=2, param_sharding=True,
        warmup_example=jnp.zeros((D,), jnp.float32), name="mfu-gw",
    )
    try:
        for lane in gw.pool.lanes:
            assert lane.engine.model_sharded
            assert lane.engine.metrics._n_devices == 8
    finally:
        gw.close()


# -- gateway lifecycle carries the sharding --------------------------------

@pytest.mark.needs_mesh8
def test_gateway_swap_preserves_model_sharding(fitted, mesh18):
    from keystone_tpu.gateway import Gateway

    gw = Gateway(
        fitted, buckets=(4, 8), n_lanes=1, param_sharding=True,
        warmup_example=jnp.zeros((D,), jnp.float32), name="shd-gw",
    )
    plain = CompiledPipeline(fitted, buckets=(4, 8), name="shd-gw-ref")
    try:
        x = batch(1)[0]
        want = np.asarray(plain.apply(batch(1), sync=True))[0]
        got = np.asarray(gw.predict(x).result(timeout=30))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        old = gw.pool.lanes[0].engine
        assert gw.rebucket(force=True)
        new = gw.pool.lanes[0].engine
        assert new is not old and new.model_sharded
        got2 = np.asarray(gw.predict(x).result(timeout=30))
        np.testing.assert_allclose(got2, want, rtol=1e-5, atol=1e-6)
    finally:
        gw.close()
