"""Persistent-compile-cache wiring (parallel/runtime.py): the cache
directory is placeable from outside through JAX_COMPILATION_CACHE_DIR
and is otherwise one fixed path inside the checkout."""

import os

import jax
import pytest

from keystone_tpu.parallel import runtime

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
