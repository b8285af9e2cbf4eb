"""The persistent compile cache (parallel/runtime.py), the package's
one executable cache: its directory is placeable from outside through
JAX_COMPILATION_CACHE_DIR and is otherwise one fixed path inside the
checkout; a fresh engine warms from it, an engine of other parameters
never reads another's programs, and an unreadable entry is recompiled."""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from compile_cache_fixtures import scratch_compile_cache

from keystone_tpu.parallel import runtime
from keystone_tpu.serving.demo_model import build_pipeline

_KNOBS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)
REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


@pytest.fixture
def cache_config_sandbox(monkeypatch):
    """Reset the module's idempotency latch AND restore the global jax
    knobs afterwards — otherwise the rest of the tier-1 suite would
    persist every tiny CPU compile into the cache dir."""
    monkeypatch.setattr(runtime, "_cache_dir", None)
    saved = {name: getattr(jax.config, name) for name in _KNOBS}
    yield
    for name, val in saved.items():
        jax.config.update(name, val)


def test_env_set_leaves_the_directory_to_jax(
    tmp_path, cache_config_sandbox, monkeypatch
):
    """With JAX_COMPILATION_CACHE_DIR set our code sets NO directory —
    jax.config.jax_compilation_cache_dir keeps whatever it held — only
    the thresholds; the function reports the env's directory."""
    d = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    sentinel = str(tmp_path / "what-jax-already-had")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)),
    )
    assert runtime.setup_compilation_cache(1.0) == d
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == sentinel
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_env_unset_uses_the_fixed_in_checkout_path(
    cache_config_sandbox, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = runtime.setup_compilation_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert got == runtime.DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == got
    assert os.path.isdir(got)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # idempotent: a second call (bench + engine both init) keeps the
    # first answer, even if the environment changed meanwhile
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert runtime.setup_compilation_cache() == got


def test_default_path_has_no_moving_part():
    """The path is part of the cache key: no temp dir, home dir, pid or
    time component may appear in it."""
    path = runtime.DEFAULT_COMPILE_CACHE_DIR
    assert os.path.isabs(path)
    # the checkout itself may live anywhere; what our code appends to
    # it is one constant name
    assert os.path.relpath(path, REPO) == ".jax_cache"


# -- the persistent compile cache as the one executable cache ----------------
#
# Every test below runs in a scratch cache directory of its own
# (compile_cache_fixtures) and restores jax's configuration afterwards.
# A "fresh engine" is built after jax.clear_caches(): its programs can
# then come only from the disk, as in a restarted process.

D = 16
EXAMPLE = np.zeros((D,), np.float32)
FEAT_IMG = 8
FLAGSHIP_IMG = 34  # > the LCS keypoint border (2*16)


def _staged_programs(compiles):
    return [name for name, _ in compiles if name.startswith("jit(staged")]


def _rows(n, seed):
    return np.random.default_rng(seed).standard_normal((n, D)).astype(
        np.float32
    )


def _raw(n, img, seed):
    return np.random.default_rng(seed).integers(
        0, 256, (n, img, img, 3), dtype=np.uint8
    )


def _model_mesh():
    from keystone_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(n_data=1, n_model=8)


def _plain_case(stack):
    fitted = build_pipeline(d=D, hidden=D, depth=2)

    def build(name):
        eng = fitted.compiled(buckets=(4, 8), name=name)
        eng.warmup(example=EXAMPLE)
        return eng

    return build, _rows(5, 0)


def _fused_case(stack):
    from keystone_tpu.serving.featurize import build_featurize_pipeline

    feat, feat_d = build_featurize_pipeline(
        img=FEAT_IMG, channels=3, filters=4, conv_size=3,
        pool_stride=4, pool_size=4, seed=3,
    )
    model = build_pipeline(d=feat_d, hidden=8, depth=2)

    def build(name):
        eng = model.compiled(buckets=(4,), featurize=feat, name=name)
        eng.warmup(example=np.zeros((FEAT_IMG, FEAT_IMG, 3), np.uint8))
        return eng

    return build, _raw(3, FEAT_IMG, 5)


def _flagship_case(stack):
    from keystone_tpu.serving.featurize import (
        build_flagship_featurize_pipeline,
    )

    flagship, feat_d = build_flagship_featurize_pipeline(
        img=FLAGSHIP_IMG, desc_dim=8, vocab=8
    )
    model = build_pipeline(d=feat_d, hidden=8, depth=2)

    def build(name):
        eng = model.compiled(buckets=(4,), featurize=flagship, name=name)
        eng.warmup(
            example=np.zeros((FLAGSHIP_IMG, FLAGSHIP_IMG, 3), np.uint8)
        )
        return eng

    return build, _raw(3, FLAGSHIP_IMG, 9)


def _sharded_case(stack):
    from keystone_tpu.parallel import mesh as mesh_lib

    fitted = build_pipeline(d=D, hidden=D, depth=2)
    mesh = _model_mesh()
    stack.enter_context(mesh_lib.use_mesh(mesh))

    def build(name):
        eng = fitted.compiled(
            buckets=(4,), name=name, param_sharding=True, mesh=mesh
        )
        eng.warmup(example=EXAMPLE)
        return eng

    return build, _rows(3, 2)


def _candidate_case(stack):
    from keystone_tpu.gateway.lifecycle import Gateway
    from keystone_tpu.observability.registry import MetricsRegistry

    incumbent = build_pipeline(d=D, hidden=D, depth=2, seed=0)
    candidate = build_pipeline(d=D, hidden=D, depth=2, seed=1)
    gw = stack.enter_context(Gateway(
        incumbent, buckets=(4,), n_lanes=1, max_delay_ms=1.0,
        warmup_example=EXAMPLE, name="cc-cand-gw",
        registry=MetricsRegistry(),
    ))

    def build(name):
        batcher = gw.build_model_batcher(candidate, name=name)
        stack.callback(batcher.close)
        batcher.engine.warmup(example=EXAMPLE)
        return batcher.engine

    return build, _rows(3, 4)


@pytest.mark.parametrize("case", [
    pytest.param(_plain_case, id="plain"),
    pytest.param(_fused_case, id="fused_featurize"),
    pytest.param(_flagship_case, id="flagship_featurize"),
    pytest.param(
        _sharded_case, id="model_sharded", marks=pytest.mark.needs_mesh8
    ),
    pytest.param(_candidate_case, id="lifecycle_candidate"),
])
def test_a_fresh_engine_warms_from_the_compile_cache(tmp_path, case):
    """A restart: the second engine's every XLA compile is a read off
    the disk, and it serves bit for bit what the first engine served."""
    with scratch_compile_cache(tmp_path / "cache") as log, \
            contextlib.ExitStack() as stack:
        build, x = case(stack)
        log.mark()
        first = build("cc-first")
        assert _staged_programs(log.since_mark())
        want = np.asarray(first.apply(x, sync=True))

        jax.clear_caches()
        log.mark()
        second = build("cc-second")
        compiles = log.since_mark()
        assert _staged_programs(compiles)
        assert {outcome for _, outcome in compiles} == {"cache_hit"}
        np.testing.assert_array_equal(
            np.asarray(second.apply(x, sync=True)), want
        )


def _other_weights_case(stack):
    first = build_pipeline(d=D, hidden=D, depth=2, seed=0)
    other = build_pipeline(d=D, hidden=D, depth=2, seed=1)

    def build(fitted, name):
        eng = fitted.compiled(buckets=(4,), name=name)
        eng.warmup(example=EXAMPLE)
        return eng

    x = _rows(3, 1)
    return (
        lambda: build(first, "cc-w-first"),
        lambda: build(other, "cc-w-other"),
        x,
        lambda: np.asarray(other._batch_run(jnp.asarray(x))),
    )


def _other_featurizer_case(stack):
    from keystone_tpu.serving.featurize import build_featurize_pipeline

    def feat(seed):
        return build_featurize_pipeline(
            img=FEAT_IMG, channels=3, filters=4, conv_size=3,
            pool_stride=4, pool_size=4, seed=seed,
        )

    (feat1, feat_d), (feat2, _) = feat(3), feat(4)
    model = build_pipeline(d=feat_d, hidden=8, depth=2)

    def build(featurize, name):
        eng = model.compiled(buckets=(4,), featurize=featurize, name=name)
        eng.warmup(example=np.zeros((FEAT_IMG, FEAT_IMG, 3), np.uint8))
        return eng

    raw = _raw(3, FEAT_IMG, 5)
    return (
        lambda: build(feat1, "cc-f-first"),
        lambda: build(feat2, "cc-f-other"),
        raw,
        lambda: np.asarray(
            model._batch_run(feat2._batch_run(jnp.asarray(raw)))
        ),
    )


def _replicated_after_sharded_case(stack):
    from keystone_tpu.parallel import mesh as mesh_lib

    fitted = build_pipeline(d=D, hidden=D, depth=2)
    mesh = _model_mesh()
    stack.enter_context(mesh_lib.use_mesh(mesh))

    def sharded():
        eng = fitted.compiled(
            buckets=(4,), name="cc-s-sharded", param_sharding=True,
            mesh=mesh,
        )
        eng.warmup(example=EXAMPLE)
        return eng

    def replicated():
        eng = fitted.compiled(buckets=(4,), name="cc-s-replicated")
        eng.warmup(example=EXAMPLE)
        return eng

    x = _rows(3, 2)
    return (
        sharded, replicated, x,
        lambda: np.asarray(fitted._batch_run(jnp.asarray(x))),
    )


def _zoo_models_case(stack):
    from keystone_tpu.observability.registry import MetricsRegistry
    from keystone_tpu.zoo import (
        BuiltModel,
        ModelRegistry,
        ModelSpec,
        ModelZoo,
    )

    heads = {
        mid: build_pipeline(d=D, hidden=8, depth=2, seed=seed)
        for mid, seed in (("alpha", 1), ("beta", 2))
    }
    registry = ModelRegistry()
    for mid, head in heads.items():
        registry.register(ModelSpec(
            model_id=mid,
            build=lambda h=head: BuiltModel(fitted=h),
            buckets=(4,), lanes=1, max_delay_ms=1.0,
            warmup_example=EXAMPLE, default=mid == "alpha",
        ))
    zoo = stack.enter_context(
        ModelZoo(registry, cse=False, metrics_registry=MetricsRegistry())
    )

    def page_in(mid):
        zoo.host([mid])
        return zoo.gateway_for(mid).pool.lanes[0].engine

    x = _rows(3, 6)
    return (
        lambda: page_in("alpha"),
        lambda: page_in("beta"),
        x,
        lambda: np.asarray(heads["beta"]._batch_run(jnp.asarray(x))),
    )


@pytest.mark.parametrize("case", [
    pytest.param(_other_weights_case, id="weights"),
    pytest.param(_other_featurizer_case, id="featurizer"),
    pytest.param(
        _replicated_after_sharded_case, id="sharded_vs_replicated",
        marks=pytest.mark.needs_mesh8,
    ),
    pytest.param(_zoo_models_case, id="zoo_models"),
])
def test_an_engine_of_other_parameters_serves_its_own(tmp_path, case):
    """With the first engine's programs on the disk, an engine of other
    weights, another featurizer, another partitioning or another zoo
    model compiles its own bucket program and serves its own outputs:
    the parameters are constants (or the partitioning is part) of the
    HLO the cache key hashes, so no entry can stand in for another."""
    with scratch_compile_cache(tmp_path / "cache") as log, \
            contextlib.ExitStack() as stack:
        build_first, build_other, x, want_other = case(stack)
        first = build_first()
        got_first = np.asarray(first.apply(x, sync=True))

        jax.clear_caches()
        log.mark()
        other = build_other()
        staged = [
            outcome for name, outcome in log.since_mark()
            if name.startswith("jit(staged")
        ]
        assert "compiled" in staged, staged
        got = np.asarray(other.apply(x, sync=True))
        np.testing.assert_allclose(got, want_other(), rtol=1e-4, atol=1e-6)
        if case is not _replicated_after_sharded_case:
            # another function, not the first engine's outputs
            assert not np.allclose(got, got_first)


def test_a_changed_bucket_list_compiles_only_the_new_bucket(tmp_path):
    """Re-bucketed from (4, 8) to (4, 16): bucket 4's program is read
    off the disk and only bucket 16's is compiled."""
    fitted = build_pipeline(d=D, hidden=D, depth=2)
    with scratch_compile_cache(tmp_path / "cache") as log:
        fitted.compiled(buckets=(4, 8), name="cc-b-1").warmup(
            example=EXAMPLE
        )
        jax.clear_caches()
        engine = fitted.compiled(buckets=(4, 16), name="cc-b-2")
        log.mark()
        engine.warmup(example=EXAMPLE, buckets=[4])
        kept = log.since_mark()
        assert _staged_programs(kept)
        assert {outcome for _, outcome in kept} == {"cache_hit"}
        log.mark()
        engine.warmup(example=EXAMPLE, buckets=[16])
        new = [
            outcome for name, outcome in log.since_mark()
            if name.startswith("jit(staged")
        ]
        assert new and set(new) == {"compiled"}
        x = _rows(11, 3)
        np.testing.assert_allclose(
            np.asarray(engine.apply(x, sync=True)),
            np.asarray(fitted._batch_run(jnp.asarray(x))),
            rtol=1e-5, atol=1e-6,
        )


def test_an_unreadable_cache_entry_recompiles_and_serves(tmp_path):
    """Every entry overwritten with garbage: jax warns, compiles the
    programs again and the engine serves what the first one served."""
    fitted = build_pipeline(d=D, hidden=D, depth=2)
    x = _rows(5, 4)
    cache = tmp_path / "cache"
    with scratch_compile_cache(cache) as log:
        first = fitted.compiled(buckets=(4, 8), name="cc-u-1")
        first.warmup(example=EXAMPLE)
        want = np.asarray(first.apply(x, sync=True))
        entries = [p for p in cache.iterdir() if p.is_file()]
        assert entries
        for path in entries:
            path.write_bytes(b"not a cache entry at all")

        jax.clear_caches()
        log.mark()
        with pytest.warns(
            UserWarning, match="Error reading persistent compilation cache"
        ):
            second = fitted.compiled(buckets=(4, 8), name="cc-u-2")
            second.warmup(example=EXAMPLE)
            got = np.asarray(second.apply(x, sync=True))
        staged = [
            outcome for name, outcome in log.since_mark()
            if name.startswith("jit(staged")
        ]
        assert staged and set(staged) == {"compiled"}
        np.testing.assert_array_equal(got, want)


def test_an_engine_warmed_from_the_cache_registers_its_cost_models(
    tmp_path,
):
    """The MFU/roofline/goodput inputs survive a warm start: the cost
    models of an engine whose programs came off the disk are the first
    engine's, bucket for bucket."""
    fitted = build_pipeline(d=D, hidden=D, depth=2)
    with scratch_compile_cache(tmp_path / "cache"):
        first = fitted.compiled(buckets=(4, 8), name="cc-cm-1")
        first.warmup(example=EXAMPLE)
        jax.clear_caches()
        second = fitted.compiled(buckets=(4, 8), name="cc-cm-2")
        second.warmup(example=EXAMPLE)
    assert sorted(first.metrics.cost_models) == [4, 8]
    assert second.metrics.cost_models == first.metrics.cost_models


def test_off_spec_input_traces_its_own_program_and_keeps_the_bucket(
    tmp_path,
):
    """An input off the warmed spec (int32 rows) on an engine warmed
    from the cache traces its own program on the bucket's jit function,
    as a cold engine would; the on-spec program stays installed and
    on-spec traffic compiles nothing more."""
    fitted = build_pipeline(d=D, hidden=D, depth=2)
    with scratch_compile_cache(tmp_path / "cache"):
        fitted.compiled(buckets=(4, 8), name="cc-os-1").warmup(
            example=EXAMPLE
        )
        jax.clear_caches()
        engine = fitted.compiled(buckets=(4, 8), name="cc-os-2")
        engine.warmup(example=EXAMPLE)
        assert engine.metrics.compile_count == 2
        installed = engine._fns[4]

        x_int = np.arange(3 * D, dtype=np.int32).reshape(3, D)
        out = np.asarray(engine.apply(x_int, sync=True))
        np.testing.assert_allclose(
            out, np.asarray(fitted._batch_run(jnp.asarray(x_int))),
            rtol=1e-5, atol=1e-6,
        )
        assert engine.metrics.compile_count == 3
        assert engine._fns[4] is installed

        engine.apply(_rows(2, 5), sync=True)
        again = np.arange(2 * D, dtype=np.int32).reshape(2, D)
        engine.apply(again, sync=True)
        assert engine.metrics.compile_count == 3
