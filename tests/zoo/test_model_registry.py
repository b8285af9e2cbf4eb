"""ModelSpec/ModelRegistry naming plane: id validation, the single
default model, duplicate rejection, UnknownModel's typed payload, and
the ``--zoo`` JSON spec loader."""

import json

import pytest

from keystone_tpu.zoo import (
    BuiltModel,
    ModelRegistry,
    ModelSpec,
    UnknownModel,
    load_zoo_spec,
)


def _spec(mid, **kw):
    return ModelSpec(
        model_id=mid, build=lambda: BuiltModel(fitted=object()), **kw
    )


def test_register_get_and_insertion_order():
    reg = ModelRegistry()
    reg.register(_spec("alpha"))
    reg.register(_spec("beta"))
    assert reg.ids() == ("alpha", "beta")
    assert reg.get("alpha").model_id == "alpha"
    assert "beta" in reg and "gamma" not in reg
    assert len(reg) == 2


def test_default_model_first_registered_unless_flagged():
    reg = ModelRegistry()
    reg.register(_spec("alpha"))
    reg.register(_spec("beta", default=True))
    assert reg.default_id == "beta"
    # no default flag anywhere -> the first registered
    reg2 = ModelRegistry((_spec("a"), _spec("b")))
    assert reg2.default_id == "a"
    assert ModelRegistry().default_id is None


def test_duplicate_id_and_second_default_rejected():
    reg = ModelRegistry()
    reg.register(_spec("alpha", default=True))
    with pytest.raises(ValueError, match="already registered"):
        reg.register(_spec("alpha"))
    with pytest.raises(ValueError, match="default model is already"):
        reg.register(_spec("beta", default=True))


@pytest.mark.parametrize(
    "bad", ["", "-leading", "has space", "slash/id", "x" * 65]
)
def test_model_id_charset_enforced(bad):
    # ids ride URL paths, and metric labels
    with pytest.raises(ValueError, match="model id"):
        _spec(bad)


def test_spec_normalizes_buckets_and_rejects_nonsense():
    spec = _spec("m", buckets=(32, 8, 8))
    assert spec.buckets == (8, 32)
    with pytest.raises(ValueError, match="buckets"):
        _spec("m", buckets=(0, 4))
    with pytest.raises(ValueError, match="lane"):
        _spec("m", lanes=0)


def test_unknown_model_carries_registered_ids():
    reg = ModelRegistry((_spec("alpha"), _spec("beta")))
    with pytest.raises(UnknownModel) as ei:
        reg.get("nope")
    assert ei.value.model_id == "nope"
    assert ei.value.registered == ("alpha", "beta")
    # it IS a KeyError, so dict-style call sites keep working
    assert isinstance(ei.value, KeyError)


def test_load_zoo_spec(tmp_path):
    path = tmp_path / "zoo.json"
    path.write_text(json.dumps({"models": [
        {"name": "alpha", "d": 12, "buckets": [4, 8], "lanes": 1,
         "default": True, "pinned": True, "slo_latency_ms": 250,
         "expected_sizes": {"1": 500, "8": 12}},
        {"name": "beta", "d": 12},
    ]}))
    reg = load_zoo_spec(str(path))
    assert reg.ids() == ("alpha", "beta")
    assert reg.default_id == "alpha"
    alpha = reg.get("alpha")
    assert alpha.pinned is True
    assert alpha.buckets == (4, 8)
    assert alpha.slo_latency_s == pytest.approx(0.25)
    # JSON object keys are strings; the spec normalizes them to ints
    assert alpha.expected_sizes == {1: 500, 8: 12}


def test_load_zoo_spec_rejects_empty_and_bad_featurize(tmp_path):
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"models": []}))
    with pytest.raises(ValueError, match="no 'models'"):
        load_zoo_spec(str(empty))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"models": [
        {"name": "m", "device_featurize": "warp-drive"}
    ]}))
    with pytest.raises(ValueError, match="device_featurize"):
        load_zoo_spec(str(bad))
