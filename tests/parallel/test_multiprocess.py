"""REAL multi-process execution of the multi-host runtime: two OS
processes, each with 4 virtual CPU devices, joined into one 8-device
runtime via jax.distributed — then a sharded BlockLS fit over the
process-spanning mesh, checked against a host numpy solve in each
process (reference substrate: bin/run-pipeline.sh:9-55 launches one JVM
per machine; here one SPMD process per host, parallel/runtime.py).

Also unit-tests the initialize() failure contract: partial config is a
clear error, and auto-detect failure on something that looks like a pod
raises instead of silently degrading to single-host.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = r"""
import os
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel import runtime
from keystone_tpu.parallel.dataset import Dataset
from keystone_tpu.ops.learning import BlockLeastSquaresEstimator

runtime.initialize()  # from COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID
assert jax.process_count() == 2, jax.process_count()
assert jax.local_device_count() == 4, jax.local_device_count()
assert jax.device_count() == 8, jax.device_count()

mesh = mesh_lib.make_mesh()  # (8, 1) global mesh spanning both processes
N, D, K = 512, 96, 5
rng = np.random.default_rng(0)
Xh = rng.standard_normal((N, D)).astype(np.float32)
Yh = Xh @ rng.standard_normal((D, K)).astype(np.float32)
sh = NamedSharding(mesh, P("data"))
X = jax.make_array_from_callback((N, D), sh, lambda idx: Xh[idx])
Y = jax.make_array_from_callback((N, K), sh, lambda idx: Yh[idx])

with mesh_lib.use_mesh(mesh):
    est = BlockLeastSquaresEstimator(block_size=D, num_iter=1, lam=0.0)
    model = est.fit(Dataset.from_array(X, n=N), Dataset.from_array(Y, n=N))

# host reference: centered unregularized LS (what one pass over one
# full-width block solves exactly)
Xc = Xh - Xh.mean(0)
Yc = Yh - Yh.mean(0)
Wref = np.linalg.lstsq(Xc, Yc, rcond=None)[0]
# model.W is replicated; compare on device so no host gather is needed
err = float(jax.numpy.abs(model.W - jax.numpy.asarray(Wref)).max())
assert err < 1e-2, err
print("MPOK", jax.process_index(), err, flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_sharded_fit():
    """Two real processes x 4 virtual CPU devices -> one 8-device mesh,
    sharded BlockLS fit, result matches the host solve in each process."""
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            NUM_PROCESSES="2",
            PROCESS_ID=str(pid),
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        # the parent conftest's virtual-device env must not leak through
        env.pop("KEYSTONE_TPU_TEST_REAL", None)
        env.pop("TPU_WORKER_HOSTNAMES", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=REPO,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process fit timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert "MPOK" in out, f"process {pid} missing OK marker:\n{out}"


def _fresh_runtime():
    from keystone_tpu.parallel import runtime

    runtime._decision = None
    return runtime


def test_partial_config_is_clear_error(monkeypatch):
    runtime = _fresh_runtime()
    try:
        monkeypatch.setenv("NUM_PROCESSES", "2")
        monkeypatch.delenv("COORDINATOR_ADDRESS", raising=False)
        monkeypatch.delenv("PROCESS_ID", raising=False)
        with pytest.raises(ValueError, match="COORDINATOR_ADDRESS"):
            runtime.initialize()
    finally:
        runtime._decision = "single-host"  # don't poison later tests


def test_pod_detection_refuses_silent_degrade(monkeypatch):
    runtime = _fresh_runtime()
    try:
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-a,host-b")
        for v in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
            monkeypatch.delenv(v, raising=False)
        # auto-detect init fails in this CPU test process (backend is
        # already up / no cluster metadata); on a pod that must raise
        with pytest.raises(RuntimeError, match="multi-host pod"):
            runtime.initialize()
    finally:
        runtime._decision = "single-host"


def test_single_host_start_touches_no_network(monkeypatch):
    """The chip machine's environment (TPU_WORKER_HOSTNAMES=localhost,
    no coordinator trio): initialize() decides single-host from the
    environment alone — jax.distributed's auto-detect, which would
    query the metadata server there, is never called."""
    import jax

    runtime = _fresh_runtime()
    try:
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        for v in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID",
                  "TPU_PROCESS_ADDRESSES", "MEGASCALE_NUM_SLICES",
                  "KEYSTONE_POD"):
            monkeypatch.delenv(v, raising=False)

        def boom(*a, **k):
            raise AssertionError("jax.distributed.initialize was called")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        assert runtime.initialize() == "single-host"
        assert runtime.initialize() == "single-host"  # idempotent
    finally:
        runtime._decision = "single-host"


def test_run_pod_flag_selects_pod_autodetect(monkeypatch):
    """bin/run-pod exports KEYSTONE_POD=1: the environment then says
    pod, auto-detect runs, and its failure raises."""
    runtime = _fresh_runtime()
    try:
        monkeypatch.setenv("KEYSTONE_POD", "1")
        for v in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID",
                  "TPU_WORKER_HOSTNAMES", "TPU_PROCESS_ADDRESSES",
                  "MEGASCALE_NUM_SLICES"):
            monkeypatch.delenv(v, raising=False)
        with pytest.raises(RuntimeError, match="multi-host pod"):
            runtime.initialize()
    finally:
        runtime._decision = "single-host"
