"""Lazy PEP 562 package exports (keystone_tpu/_lazy.py): re-exported
names, on-demand submodule access, and the error-discrimination contract
(missing submodule -> AttributeError; missing DEPENDENCY inside a real
submodule -> the original ModuleNotFoundError, not a masked
AttributeError). The laziness exists so the streaming loader's spawn
decode workers never import jax."""

import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reexports_and_submodule_access():
    import keystone_tpu

    assert keystone_tpu.Pipeline.__name__ == "Pipeline"
    assert keystone_tpu.Dataset.__name__ == "Dataset"
    # eager imports used to bind subpackages as side effects; the lazy
    # fallback must keep attribute-style submodule access working
    assert keystone_tpu.workflow.__name__ == "keystone_tpu.workflow"
    assert keystone_tpu.loaders.CsvDataLoader.__name__ == "CsvDataLoader"


def test_missing_attribute_is_attribute_error():
    import keystone_tpu

    with pytest.raises(AttributeError, match="no attribute"):
        keystone_tpu.definitely_not_a_thing


def test_streaming_import_stays_light():
    """Importing the streaming loader must not pull the heavy compute
    modules through the package __init__ (spawn decode workers pay this
    import)."""
    import subprocess

    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import keystone_tpu.loaders.streaming
        heavy = [m for m in sys.modules
                 if m.startswith('keystone_tpu.')
                 and ('workflow' in m or 'dataset' in m or '.ops' in m)]
        assert not heavy, heavy
        # the point of the laziness: no jax either
        print('JAXFREE' if 'jax' not in sys.modules else 'JAXLOADED')
        print('LIGHT')
    """ % (REPO,))
    env = {k: v for k, v in os.environ.items()}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "LIGHT" in out.stdout
    # importing the streaming loader must not pull jax in
    assert "JAXFREE" in out.stdout, out.stdout


def test_missing_dependency_stays_loud(tmp_path, monkeypatch):
    """A submodule that exists but fails on a missing dependency must
    surface the REAL ModuleNotFoundError, not an AttributeError claiming
    the submodule doesn't exist."""
    pkg = tmp_path / "fakepkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(textwrap.dedent("""
        from keystone_tpu._lazy import make_getattr
        _EXPORTS = {}
        __getattr__ = make_getattr(__name__, _EXPORTS)
    """))
    (pkg / "needs_dep.py").write_text("import not_a_real_dependency\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    import fakepkg

    with pytest.raises(ModuleNotFoundError, match="not_a_real_dependency"):
        fakepkg.needs_dep
    with pytest.raises(AttributeError, match="no attribute"):
        fakepkg.not_a_submodule
