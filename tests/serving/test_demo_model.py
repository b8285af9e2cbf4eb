"""The served demo model is a function of ``(d, hidden, depth, seed)``:
``serve-gateway``, ``serve-loadgen``, ``serve-capacity-plan``,
``serve-aot-build`` and a zoo spec given the same arguments hold the same
weights. The expected values were recorded at commit 7243142, where the
model lived in ``keystone_tpu/serving/bench.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.serving.demo_model import (
    affine_head,
    build_pipeline,
    build_split_pipeline,
)

X = (np.arange(16, dtype=np.float32).reshape(2, 8) - 7.5) / 8.0

RECORDED = {
    0: [0.13216590881347656, -0.2671299874782562, -0.3625141978263855,
        -0.424707293510437, -0.09808256477117538, 0.049746621400117874,
        0.4399847090244293, -0.41358447074890137, -0.38433369994163513,
        0.1114029511809349, 0.19113609194755554, 0.5752105712890625,
        0.5291927456855774, -0.290485680103302, -0.5114286541938782,
        0.4833996295928955],
    3: [-0.10548187047243118, -0.40823182463645935, 0.17999430000782013,
        0.16116158664226532, 0.40384379029273987, -0.3199561536312103,
        -0.09524335712194443, 0.0078905513510108, -0.10622904449701309,
        -0.11942391097545624, 0.3242282271385193, 0.2186485081911087,
        0.44304385781288147, -0.07782511413097382, 0.2562488615512848,
        -0.3121976852416992],
}


def _run(fitted, x):
    return np.asarray(fitted._batch_run(jnp.asarray(x)))


@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_same_arguments_same_model_as_recorded(seed):
    got = _run(build_pipeline(d=8, hidden=16, depth=3, seed=seed), X)
    np.testing.assert_allclose(
        got.ravel(), RECORDED[seed], rtol=1e-5, atol=1e-6
    )


def test_defaults_are_the_recorded_model():
    x = ((np.arange(256, dtype=np.float32) - 127.5) / 128.0)[None]
    got = _run(build_pipeline(), x).ravel()
    np.testing.assert_allclose(
        got[:8],
        [-0.1558716893196106, -0.3213793635368347, -0.2351122945547104,
         -0.1545795500278473, 0.0657123252749443, -0.27743008732795715,
         0.02671222761273384, 0.4806636869907379],
        rtol=1e-5, atol=1e-6,
    )
    assert float(np.float64(got).sum()) == pytest.approx(
        2.156311593251303, abs=1e-3
    )


def test_split_form_serves_the_same_outputs():
    base, w, b = build_split_pipeline(d=8, hidden=16, depth=3, seed=3)
    np.testing.assert_array_equal(
        _run(base.and_then(affine_head(w, b)), X),
        _run(build_pipeline(d=8, hidden=16, depth=3, seed=3), X),
    )
