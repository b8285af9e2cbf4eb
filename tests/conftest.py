"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests run Spark in local[n] mode with multi-partition RDDs
standing in for a cluster (SURVEY.md §4); the equivalent here is
--xla_force_host_platform_device_count=8 so sharding/collective code paths are
exercised without TPU hardware. Must be set before jax initializes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# tests/ itself, so suites in subdirectories can import shared fixture
# helpers (jpeg_fixtures) regardless of collection order
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from keystone_tpu.parallel.virtual import provision_devices  # noqa: E402

# Tests run on the virtual CPU mesh by default (fast, deterministic, no
# TPU needed). KEYSTONE_TPU_TEST_REAL=1 runs the same suite against the
# real accelerator instead — the hardware-sanity sweep that catches
# TPU-only failures (e.g. DEFAULT-precision f32 matmuls) CPU runs hide.
_REAL = os.environ.get("KEYSTONE_TPU_TEST_REAL") == "1"
if not _REAL:
    provision_devices(8)
else:
    import jax

    if jax.devices()[0].platform == "cpu":
        raise RuntimeError(
            "KEYSTONE_TPU_TEST_REAL=1 but no accelerator is attached — "
            "this sweep exists to catch hardware-only failures; running "
            "it on CPU would silently prove nothing"
        )

import pytest  # noqa: E402

# Every entry point that sets the compile cache up installs the
# runtime's compile listeners, and some tests call one: install them for
# all, so that what a test sees of them (``runtime.*`` spans in the ring
# of an enabled tracer) does not depend on which test ran before it.
from keystone_tpu.parallel import runtime as _runtime  # noqa: E402

_runtime.install_compile_telemetry()


def pytest_collection_modifyitems(config, items):
    """One shared gate for @pytest.mark.needs_mesh8 — sharded tests skip
    on single-chip hardware (the KEYSTONE_TPU_TEST_REAL sweep) instead of
    each module rolling its own skipif."""
    import jax

    if len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(reason="needs the 8-device (virtual) mesh")
    for item in items:
        if "needs_mesh8" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def reset_pipeline_env():
    """Each test gets a fresh global pipeline environment (reference:
    PipelineContext.afterEach calls PipelineEnv.reset)."""
    from keystone_tpu.workflow.executor import PipelineEnv
    from keystone_tpu.parallel import mesh as mesh_lib

    PipelineEnv.get_or_create().reset()
    mesh_lib.set_mesh(None)
    yield
    PipelineEnv.get_or_create().reset()
    mesh_lib.set_mesh(None)


@pytest.fixture
def mesh8():
    """An 8-way data-parallel mesh over the virtual CPU devices (or
    whatever the real hardware has under KEYSTONE_TPU_TEST_REAL=1)."""
    import jax

    from keystone_tpu.parallel import mesh as mesh_lib

    n = min(8, len(jax.devices())) if _REAL else 8
    m = mesh_lib.make_mesh(n_data=n, devices=jax.devices()[:n])
    with mesh_lib.use_mesh(m):
        yield m
