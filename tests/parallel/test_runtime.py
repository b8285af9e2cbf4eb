"""Multi-slice mesh shape logic + an end-to-end sharded fit on a
(dcn, data, model) mesh over the 8 virtual CPU devices (reference
equivalent: the Spark cluster substrate, SURVEY.md §2.10 comm-backend row;
multi-host orchestration via jax.distributed)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel.runtime import (
    make_multislice_mesh,
    multislice_shape,
)

# The 2x2x2 multislice mesh needs 8 devices — present on the virtual CPU
# mesh, absent on a single real chip (shared gate in tests/conftest.py)
mesh8 = pytest.mark.needs_mesh8


def test_multislice_shape_logic():
    assert multislice_shape(64, n_slices=4, n_model=2) == (4, 8, 2)
    assert multislice_shape(8, n_slices=2, n_model=1) == (2, 4, 1)
    assert multislice_shape(256, n_slices=4, n_model=8) == (4, 8, 8)
    with pytest.raises(ValueError):
        multislice_shape(8, n_slices=3)
    with pytest.raises(ValueError):
        multislice_shape(8, n_slices=2, n_model=3)


@mesh8
def test_multislice_mesh_axes():
    mesh = make_multislice_mesh(n_slices=2, n_model=2)
    assert mesh.axis_names == ("dcn", "data", "model")
    assert mesh.shape["dcn"] == 2
    assert mesh.shape["data"] == 2
    assert mesh.shape["model"] == 2
    # data sharding spans dcn x data
    assert mesh_lib.n_data_shards(mesh) == 4
    sh = mesh_lib.data_sharding(mesh)
    assert sh.spec == P(("dcn", "data"), None)


@mesh8
def test_block_ls_fit_on_multislice_mesh():
    """The solver's Gram psums must compile + run with examples sharded
    over (dcn, data) and features over model — the full dp x tp x slice
    layout."""
    from keystone_tpu.ops.learning import BlockLeastSquaresEstimator
    from keystone_tpu.parallel.dataset import Dataset

    mesh = make_multislice_mesh(n_slices=2, n_model=2)
    with mesh_lib.use_mesh(mesh):
        n, d, k = 64, 16, 4
        rng = np.random.default_rng(0)
        X_host = rng.standard_normal((n, d)).astype(np.float32)
        W_true = rng.standard_normal((d, k)).astype(np.float32)
        Y_host = X_host @ W_true
        X = jax.device_put(
            jnp.asarray(X_host),
            NamedSharding(mesh, P(("dcn", "data"), "model")),
        )
        Y = jax.device_put(
            jnp.asarray(Y_host),
            NamedSharding(mesh, P(("dcn", "data"), None)),
        )
        est = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=0.01)
        model = est.fit(Dataset.from_array(X), Dataset.from_array(Y))
        preds = model.apply_batch(Dataset.from_array(X, n=n))
        err = float(jnp.abs(preds.padded() - Y).max())
        assert err < 1.0, err


def test_initialize_single_host_is_noop():
    from keystone_tpu.parallel import runtime

    runtime.initialize()  # no cluster env -> logs and returns
    runtime.initialize()  # idempotent


def test_claim_accelerator_one_process_per_chip(tmp_path, monkeypatch):
    """A process that will use the TPU takes a host-wide lock; a second
    claimant fails at once with a message naming the holder (N
    serve-gateway replicas on one host cannot share one chip). A CPU
    process claims nothing."""
    import jax

    from keystone_tpu.parallel import runtime

    lock = str(tmp_path / "chip.lock")
    monkeypatch.setattr(runtime, "_chip_lock", None)
    assert runtime.claim_accelerator(lock) is False  # JAX_PLATFORMS=cpu
    assert not os.path.exists(lock)

    saved = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu,cpu")
        assert runtime.claim_accelerator(lock) is True
        assert runtime.claim_accelerator(lock) is True  # idempotent
        held = runtime._chip_lock
        # a second claimant (another replica): a fresh open file
        # description conflicts exactly like another process would
        monkeypatch.setattr(runtime, "_chip_lock", None)
        with pytest.raises(RuntimeError, match=f"pid {os.getpid()}"):
            runtime.claim_accelerator(lock)
        held.close()  # the holder exits: the chip is free again
        assert runtime.claim_accelerator(lock) is True
        runtime._chip_lock.close()
    finally:
        jax.config.update("jax_platforms", saved)
