"""jax's persistent compile cache in a directory of one test's own, with
a fresh ``runtime.compile_log()`` to read what each compilation inside
it did (``compiled`` by XLA, or a ``cache_hit`` off the disk)."""

import contextlib

import jax
from jax import monitoring
from jax.experimental.compilation_cache import compilation_cache

from keystone_tpu.parallel import runtime

_KNOBS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


def register_listeners(telemetry):
    monitoring.register_scalar_listener(telemetry.on_entry)
    monitoring.register_event_listener(telemetry.on_event)
    monitoring.register_event_duration_secs_listener(telemetry.on_exit)


def unregister_listeners(telemetry):
    monitoring.unregister_scalar_listener(telemetry.on_entry)
    monitoring.unregister_event_listener(telemetry.on_event)
    monitoring.unregister_event_duration_listener(telemetry.on_exit)


class CompileRecords:
    """Reads the test's own compile log, which ``mark`` empties."""

    def __init__(self, telemetry):
        self._telemetry = telemetry

    def mark(self) -> None:
        with self._telemetry._lock:
            self._telemetry._log.clear()

    @staticmethod
    def since_mark():
        """``(fun_name, outcome)`` of every backend-compile phase
        logged since the last ``mark``."""
        log = runtime.compile_log()
        assert len(log) < runtime.COMPILE_LOG_CAPACITY, (
            "the compile log wrapped; the test compiles too much to read"
        )
        return [
            (r["fun_name"], r["outcome"])
            for r in log if r["phase"] == "compile"
        ]


@contextlib.contextmanager
def scratch_compile_cache(directory):
    """Cache every program (no size or time floor) under ``directory``,
    starting from empty in-memory caches, so the first compilation of a
    program in the block is XLA's and every later one, after
    ``jax.clear_caches()``, can only come from the disk. The jax
    configuration and the process's own listeners are put back after."""
    saved = {name: getattr(jax.config, name) for name in _KNOBS}
    before = runtime._telemetry
    if before is not None:
        unregister_listeners(before)
    runtime._telemetry = None
    jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    jax.clear_caches()
    runtime.install_compile_telemetry()
    mine = runtime._telemetry
    try:
        yield CompileRecords(mine)
    finally:
        unregister_listeners(mine)
        runtime._telemetry = before
        if before is not None:
            register_listeners(before)
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
