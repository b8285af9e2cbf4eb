"""Cross-model featurize CSE: grouping is by content fingerprint
(``featurize_token``), and a ``SharedPrefixEngine`` computes the
shared prefix once per window — one trace per bucket for the WHOLE
group, outputs bit-matching each member's solo engine."""

import numpy as np
import pytest

from keystone_tpu.serving.demo_model import build_pipeline
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.serving.featurize import build_featurize_pipeline
from keystone_tpu.zoo import SharedPrefixEngine, featurize_groups

IMG = 8


@pytest.fixture(scope="module")
def featurize():
    feat, feat_d = build_featurize_pipeline(img=IMG)
    return feat, feat_d


def _raws(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, IMG, IMG, 3), dtype=np.uint8)


def test_featurize_groups_by_content_not_name(featurize):
    feat, _ = featurize
    # a second, independently built chain with the SAME seed carries
    # the same params -> same token -> same group
    twin, _ = build_featurize_pipeline(img=IMG)
    other, _ = build_featurize_pipeline(img=IMG, seed=12)
    groups = featurize_groups(
        {"a": feat, "b": twin, "zzz": other}
    )
    assert ("a", "b") in groups
    assert ("zzz",) in groups


def test_featurize_groups_unfingerprintable_hosts_solo(featurize):
    feat, _ = featurize

    class Opaque:
        """No fittable structure: featurize_token raises."""

    groups = featurize_groups({"a": feat, "weird": Opaque()})
    # it can't PROVE equality with anything, so it never shares
    assert ("weird",) in groups
    assert ("a",) in groups


def test_shared_prefix_engine_matches_solo_per_model(featurize):
    feat, feat_d = featurize
    heads = {
        "alpha": build_pipeline(d=feat_d, hidden=16, depth=2, seed=1),
        "beta": build_pipeline(d=feat_d, hidden=16, depth=2, seed=2),
    }
    buckets = (2, 4)
    shared = SharedPrefixEngine(
        feat, heads, buckets, donate=False, name="cse-shared"
    )
    raws = _raws(3, seed=3)
    out = shared.apply(raws, sync=True)
    assert sorted(out) == ["alpha", "beta"]
    for mid, head in heads.items():
        solo = CompiledPipeline(
            head, buckets, featurize=feat, aot_store=None,
            donate=False, name=f"cse-solo-{mid}",
        )
        want = np.asarray(solo.apply(_raws(3, seed=3), sync=True))
        np.testing.assert_allclose(
            np.asarray(out[mid]), want, rtol=1e-4, atol=1e-5
        )


def test_shared_prefix_traces_once_per_bucket(featurize):
    feat, feat_d = featurize
    heads = {
        "alpha": build_pipeline(d=feat_d, hidden=16, depth=2, seed=1),
        "beta": build_pipeline(d=feat_d, hidden=16, depth=2, seed=2),
    }
    shared = SharedPrefixEngine(
        feat, heads, (2, 4), donate=False, name="cse-counters"
    )
    shared.apply(_raws(3), sync=True)   # bucket 4: first trace
    shared.apply(_raws(4), sync=True)   # bucket 4 again: cached
    shared.apply(_raws(2), sync=True)   # bucket 2: second trace
    # ONE program per bucket serves the whole group, where solo
    # hosting traces one per model and bucket
    assert shared.metrics.compiles.total == 2
    assert shared.metrics.dispatches.total == 3


def test_shared_prefix_engine_rejects_bad_compositions(featurize):
    feat, feat_d = featurize
    head = build_pipeline(d=feat_d, hidden=16, depth=2, seed=1)
    with pytest.raises(ValueError, match="featurize prefix"):
        SharedPrefixEngine(None, {"a": head}, (2,))
    with pytest.raises(ValueError, match="at least one head"):
        SharedPrefixEngine(feat, {}, (2,))
    with pytest.raises(ValueError, match="param_sharding"):
        SharedPrefixEngine(
            feat, {"a": head}, (2,), param_sharding=True
        )
