"""Virtual-device provisioning for multi-chip code paths without chips.

The reference tests simulate a cluster with multi-partition local RDDs
(SURVEY.md §4); the JAX equivalent is a virtual n-device CPU platform.
This is the ONE place that knows how to provision it — used by both
tests/conftest.py and the driver's ``dryrun_multichip`` entry point so the
two can't drift.

JAX constraint: ``jax_platforms`` / ``jax_num_cpu_devices`` must be set
before the backend initializes. Provisioning is always the virtual CPU
platform and never probes for real chips: a chip belongs to one process
at a time, so a throwaway child that initialized the backend to count
devices would hold the chip the parent then needs. Real chips are driven
by ``chip_smoke.py``, which takes whatever ``jax.devices()`` reports.
"""

from __future__ import annotations


def backend_initialized() -> bool:
    """Whether a jax backend already exists, WITHOUT creating one."""
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def provision_devices(n_devices: int) -> None:
    """Ensure ``jax.devices()`` will return >= n_devices by switching an
    uninitialized process to a virtual CPU platform with exactly
    ``n_devices`` devices. An already-initialized backend is kept when
    it has enough devices (whatever its platform — callers that care
    print ``jax.devices()[0].platform``); with too few it raises (too
    late to reconfigure).
    """
    import jax

    if backend_initialized():
        have = len(jax.devices())
        if have < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices but the jax backend is already "
                f"initialized with {have}; call provision_devices() before "
                f"any jax operation (fresh process)"
            )
        return

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    have = len(jax.devices())
    if have < n_devices:
        raise RuntimeError(
            f"could not provision {n_devices} virtual CPU devices; "
            f"got {have}"
        )
