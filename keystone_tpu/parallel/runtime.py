"""Multi-host / multi-slice runtime.

The reference's distributed substrate is a Spark cluster launched by
``bin/run-pipeline.sh:9-55`` (spark-submit against $SPARK_HOME) and
provisioned by ``bin/keystone-ec2.sh``. The TPU-native equivalent is a
**SPMD process group**: one Python process per host, every process runs
the same program, ``jax.distributed.initialize`` wires them into one
runtime, and XLA collectives ride ICI within a slice and DCN across
slices. There is no driver/executor split — the "driver-side solve"
pattern of the reference becomes a replicated small computation.

Axis layout (the scaling-book recipe):

- ``dcn``   — the slice axis. Only data parallelism crosses it: per-slice
  partial Gram/gradient sums are combined with one small all-reduce over
  DCN, which is latency-tolerant.
- ``data``  — intra-slice example sharding (ICI).
- ``model`` — intra-slice feature/model-block sharding (ICI, bandwidth-
  hungry collectives stay on ICI).

Example pod launch (one command per host, e.g. via ``gcloud compute tpus
tpu-vm ssh --worker=all``)::

    python -m keystone_tpu TimitPipeline --trainLocation gs://... \
        # jax.distributed auto-detects coordinator/process ids on TPU VMs

``initialize()`` decides from the environment alone (see its
docstring): an explicit COORDINATOR_ADDRESS / NUM_PROCESSES /
PROCESS_ID trio, a pod environment (``bin/run-pod`` exports
``KEYSTONE_POD=1``; cluster metadata then supplies coordinator address
and process count), or a single host that starts no distributed
runtime and touches no network.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import sys
import threading
from typing import IO, Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

logger = logging.getLogger(__name__)

DCN_AXIS = "dcn"

# the fixed in-checkout compile-cache path used when
# JAX_COMPILATION_CACHE_DIR is unset (gitignored)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

# what initialize() decided; None until it ran
_decision: Optional[str] = None

# host-wide advisory lock a device-serving process holds for its
# lifetime (claim_accelerator); a fixed path, or the lock would not be
# host-wide
CHIP_LOCK_PATH = "/tmp/keystone_tpu_chip.lock"
_chip_lock: Optional[IO[str]] = None
_cache_dir: Optional[str] = None
_aot_dir: Optional[str] = None


# -- compilation telemetry ---------------------------------------------------

# the last phases kept for compile_log(): one small record each
COMPILE_LOG_CAPACITY = 1024

# jax.monitoring's timed phases of one compilation (jax/_src/dispatch.py:
# a scalar event at a phase's entry, a duration event at its exit, same
# thread, LIFO) -> the phase's name here
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# fired inside the backend phase when the executable came from the
# persistent cache (jax/_src/compiler.py)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _OpenPhase:
    """A compilation phase between its entry and its exit event."""

    __slots__ = (
        "phase", "fun", "start_s", "owner", "where", "span", "nested_s",
        "cache_hit",
    )

    def __init__(self, phase, fun, start_s, owner, where, span):
        self.phase = phase
        self.fun = fun
        self.start_s = start_s  # epoch seconds, jax's own reading
        self.owner = owner
        self.where = where
        self.span = span
        self.nested_s = 0.0  # seconds of the phases nested in this one
        self.cache_hit = False


def _asker(frame) -> Tuple[str, str]:
    """Who asked for a compilation: ``("program", "module:function")``
    of the innermost ``keystone_tpu`` frame on the calling thread's
    stack, else ``("other", "")`` (a harness's generator, a plain
    reference, user code). This module asks for no compilation, so its
    own frames (the listener's) do not count."""
    while frame is not None:
        module = frame.f_globals.get("__name__", "")
        if module.startswith("keystone_tpu.") and module != __name__:
            code = frame.f_code
            return "program", "%s:%s" % (
                module, getattr(code, "co_qualname", code.co_name)
            )
        frame = frame.f_back
    return "other", ""


def _guarded(listener):
    """A listener that raises is caught and counted, never propagated
    into the compilation that fired it."""

    @functools.wraps(listener)
    def safe(self, event, *args, **kwargs):
        try:
            listener(self, event, *args, **kwargs)
        except Exception:
            logger.debug("compile listener failed on %s", event,
                         exc_info=True)
            try:
                *_, errors = self.families()
                errors.inc((listener.__name__,))
            except Exception:
                pass

    return safe


class _CompileTelemetry:
    """The ``jax.monitoring`` listeners behind the
    ``keystone_runtime_*`` families, ``compile_log()`` and the
    ``runtime.trace`` / ``.lower`` / ``.compile`` spans. The events fire
    where jax traces, lowers or compiles and never per dispatch, so a
    warm step pays nothing; the frame walk, the thread-local stack and
    the log's lock are here and nowhere on a hot path."""

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._log: Deque[Dict[str, Any]] = (
            collections.deque(maxlen=COMPILE_LOG_CAPACITY)
        )  # guarded-by: _lock

    @staticmethod
    def families():
        """(requests, backend seconds, trace/lower seconds, listener
        errors) of the global registry: get-or-create, so that a
        registry reset between two compilations loses no event."""
        from keystone_tpu.observability.registry import get_global_registry

        reg = get_global_registry()
        return (
            reg.counter(
                "keystone_runtime_compile_requests_total",
                "backend-compile phases: XLA ran (compiled) or the "
                "executable came from the persistent cache (cache_hit)",
                labelnames=("outcome", "owner"),
            ),
            reg.counter(
                "keystone_runtime_backend_seconds_total",
                "seconds of backend-compile phases (for a cache_hit: "
                "retrieval and deserialisation)",
                labelnames=("outcome", "owner"),
            ),
            reg.counter(
                "keystone_runtime_trace_lower_seconds_total",
                "self seconds of jaxpr tracing and of lowering to MLIR",
                labelnames=("phase", "owner"),
            ),
            reg.counter(
                "keystone_runtime_listener_errors_total",
                "compile listeners that raised (caught, not propagated)",
                labelnames=("listener",),
            ),
        )

    def _stack(self) -> List[_OpenPhase]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @_guarded
    def on_entry(self, event: str, value: float, **kwargs) -> None:
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        from keystone_tpu.observability.tracing import get_tracer

        fun = str(kwargs.get("fun_name", ""))
        owner, where = _asker(sys._getframe(1))
        span = get_tracer().start_span("runtime." + phase, fun=fun)
        self._stack().append(
            _OpenPhase(phase, fun, float(value), owner, where, span)
        )

    @_guarded
    def on_event(self, event: str, **kwargs) -> None:
        if event != _CACHE_HIT_EVENT:
            return
        for open_phase in reversed(self._stack()):
            if open_phase.phase == "compile":
                open_phase.cache_hit = True
                return

    @_guarded
    def on_exit(self, event: str, seconds: float, **kwargs) -> None:
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        from keystone_tpu.observability.tracing import get_tracer

        seconds = float(seconds)
        stack = self._stack()
        # LIFO: the innermost open phase of this kind; one above it
        # whose exit never came (its listener raised) is closed on the way
        for i in range(len(stack) - 1, -1, -1):
            if stack[i].phase == phase:
                break
        else:
            return  # its entry was not seen: nothing to close or count
        for abandoned in reversed(stack[i + 1:]):
            get_tracer().end_span(abandoned.span)
        done = stack[i]
        del stack[i:]
        outcome = None
        if phase == "compile":
            outcome = "cache_hit" if done.cache_hit else "compiled"
            done.span.set_attr("outcome", outcome)
        get_tracer().end_span(done.span)
        # nested phases (an outer jit's trace contains its callees')
        # count once: each phase its own seconds, never the sum
        self_s = max(seconds - done.nested_s, 0.0)
        if stack:
            stack[-1].nested_s += seconds
        requests, backend_s, trace_lower_s, _ = self.families()
        if phase == "compile":
            requests.inc((outcome, done.owner))
            backend_s.inc((outcome, done.owner), self_s)
        else:
            trace_lower_s.inc((phase, done.owner), self_s)
        record = {
            "fun_name": done.fun,
            "phase": phase,
            "outcome": outcome,
            "seconds": seconds,
            "self_seconds": self_s,
            "start_s": done.start_s,
            "owner": done.owner,
            "where": done.where,
        }
        with self._lock:
            self._log.append(record)

    def log(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._log]


_telemetry: Optional[_CompileTelemetry] = None


def install_compile_telemetry() -> None:
    """Count, name and time every trace, lowering, cache load and XLA
    compile of this process from inside it (idempotent; called by
    ``setup_compilation_cache``, so every entry point that sets the
    cache up has it): the ``keystone_runtime_*`` counter families
    (registered here, so a process that compiled nothing reads 0 and not
    nothing), ``compile_log()`` and the ``runtime.trace`` /
    ``runtime.lower`` / ``runtime.compile`` spans. One listener set,
    through the public ``jax.monitoring`` register functions."""
    global _telemetry
    if _telemetry is not None:
        return
    from jax import monitoring

    telemetry = _CompileTelemetry()
    telemetry.families()
    monitoring.register_scalar_listener(telemetry.on_entry)
    monitoring.register_event_listener(telemetry.on_event)
    monitoring.register_event_duration_secs_listener(telemetry.on_exit)
    _telemetry = telemetry


def compile_log() -> List[Dict[str, Any]]:
    """The last ``COMPILE_LOG_CAPACITY`` compilation phases, oldest
    first — "which step recompiled, and who asked": ``fun_name``,
    ``phase`` (``trace`` | ``lower`` | ``compile``), ``outcome``
    (``compiled`` | ``cache_hit``; None for a trace or a lowering),
    ``seconds`` as jax reports them and ``self_seconds`` (less the
    phases nested inside), ``start_s`` (epoch), ``owner`` (``program``
    when a ``keystone_tpu`` frame asked, else ``other``) and ``where``
    (that frame as ``module:function``). Empty until
    ``install_compile_telemetry``. Served under ``/debugz``."""
    return [] if _telemetry is None else _telemetry.log()


def setup_compilation_cache(min_compile_time_secs: float = 0.0) -> str:
    """Wire up JAX's persistent XLA compilation cache (idempotent).

    A restarted server pays ZERO cold compiles for shapes it has seen:
    ``CompiledPipeline.warmup`` replays each bucket's compile from this
    on-disk cache instead of re-running XLA (seconds per program).

    The directory is placeable from outside and nowhere else: when
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    function sets no directory; when it is unset the cache lives at the
    fixed in-checkout path ``DEFAULT_COMPILE_CACHE_DIR``
    (``<repo>/.jax_cache``) — the path is part of the cache key, so a
    directory that moved between runs would never hit.
    ``min_compile_time_secs=0`` caches every program — serving wants
    even fast compiles persisted, unlike one-shot training scripts
    where tiny entries are churn.

    Returns the cache dir in use."""
    global _cache_dir
    install_compile_telemetry()
    if _cache_dir is not None:
        return _cache_dir
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_compile_time_secs),
    )
    # cache regardless of entry size
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _cache_dir = cache_dir
    logger.info("persistent compilation cache at %s", cache_dir)
    return cache_dir


def setup_aot_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Configure the AOT serialized-executable store dir (idempotent)
    — the second half of the restart story. The persistent compilation
    cache above removes the XLA *compile* from a restart but the
    process still pays trace + lowering + cache replay per bucket;
    with this store configured, ``CompiledPipeline.warmup``
    deserializes each bucket's whole executable
    (``serving/aot.py``) and a fresh replica goes from exec() to
    serving without tracing anything. The dir resolves from the
    argument, ``$KEYSTONE_AOT_CACHE``, then
    ``~/.cache/keystone_tpu/aot``.

    Returns the store dir, or None when it can't be created (the call
    is then a no-op — serving works, cold starts just compile)."""
    global _aot_dir
    if _aot_dir is not None:
        return _aot_dir
    cache_dir = (
        cache_dir
        or os.environ.get("KEYSTONE_AOT_CACHE")
        or os.path.join(
            os.path.expanduser("~"), ".cache", "keystone_tpu", "aot"
        )
    )
    try:
        # 0700: the store dir is a trust boundary (entries are pickled
        # executables — write access there is code execution in the
        # server; serving/aot.py documents the contract). Pre-existing
        # dirs keep the operator's chosen mode.
        os.makedirs(cache_dir, mode=0o700, exist_ok=True)
    except OSError as e:
        logger.info("AOT executable cache unavailable: %s", e)
        return None
    _aot_dir = cache_dir
    logger.info("AOT executable cache at %s", cache_dir)
    return cache_dir


def aot_cache_dir() -> Optional[str]:
    """The configured AOT store dir (None until ``setup_aot_cache``)."""
    return _aot_dir


def claim_accelerator(lock_path: str = CHIP_LOCK_PATH) -> bool:
    """Take this host's TPU for this process, or fail saying who holds
    it. A chip belongs to one process at a time: a second process that
    initializes the backend fails late inside libtpu or hangs waiting.
    Device-serving entry points (``serve-gateway``) call this BEFORE
    touching the backend, so N replicas spawned on one host
    (``autoscale/supervisor.py``) get an immediate error naming the
    holder instead. Advisory (``flock``), so it only sees processes that
    also claim; released when the process exits.

    Returns False without locking when this process will not use a TPU
    (``jax_platforms`` does not list it — CPU tests and smoke drills)."""
    global _chip_lock
    if "tpu" not in (jax.config.jax_platforms or "").split(","):
        return False
    if _chip_lock is not None:
        return True
    import fcntl

    f = open(lock_path, "a+")
    try:
        fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except BlockingIOError:
        f.seek(0)
        holder = f.read().strip() or "another process"
        f.close()
        raise RuntimeError(
            f"the TPU on this host is already held by {holder}; a chip "
            "belongs to one process at a time, so several replicas on "
            "one host cannot share it — run one device process per "
            "host (it can drive every chip of the host)"
        ) from None
    f.seek(0)
    f.truncate()
    f.write(f"pid {os.getpid()} ({' '.join(sys.argv[:3])})")
    f.flush()
    _chip_lock = f
    return True


def _looks_like_pod() -> bool:
    """Whether the ENVIRONMENT says this host is one of several in a
    TPU pod / multislice deployment — the situation where starting
    single-host would make every host train its own model. Read from
    variables only, never from the network: several worker hostnames
    or process addresses, more than one slice, or ``KEYSTONE_POD=1``,
    which ``bin/run-pod`` exports for Cloud TPU pods whose process grid
    is known only to the instance metadata server."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if "," in hosts:
        return True
    addrs = os.environ.get("TPU_PROCESS_ADDRESSES", "")
    if "," in addrs:
        return True
    try:
        if int(os.environ.get("MEGASCALE_NUM_SLICES", "1")) > 1:
            return True
    except ValueError:
        pass
    return os.environ.get("KEYSTONE_POD") == "1"


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> str:
    """Join this process to the multi-host runtime (idempotent), or
    decide it is a single host. Returns (and logs at INFO) what it
    decided: ``"explicit"``, ``"pod"`` or ``"single-host"``.

    The decision is read from arguments and environment alone, so a
    single-host start never waits on a network:

    - all three of COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID
      (arguments or env, set by the launch script the way
      run-pipeline.sh exported SPARK_HOME/KEYSTONE_MEM): join that
      rendezvous — ``"explicit"``. Some but not all raises
      ``ValueError`` naming what's missing.
    - a pod environment (``_looks_like_pod``): ``jax.distributed``
      auto-detects the process grid (GKE worker lists, or the Cloud
      TPU metadata server) — ``"pod"``. A failure there raises instead
      of letting every host silently train its own model.
    - neither: ``"single-host"``; ``jax.distributed`` is not called.
      (Its argument-less auto-detect treats any TPU VM with
      TPU_WORKER_HOSTNAMES set — even ``localhost`` — as a GKE cluster
      and queries ``metadata.google.internal`` for the coordinator.)
    """
    global _decision
    if _decision is not None:
        return _decision
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])

    explicit = {
        "COORDINATOR_ADDRESS": coordinator_address,
        "NUM_PROCESSES": num_processes,
        "PROCESS_ID": process_id,
    }
    given = [k for k, v in explicit.items() if v is not None]
    missing = [k for k, v in explicit.items() if v is None]
    if given and missing:
        raise ValueError(
            "partial multi-host config: "
            f"{'/'.join(given)} set but {'/'.join(missing)} missing — "
            "set all three of COORDINATOR_ADDRESS / NUM_PROCESSES / "
            "PROCESS_ID (env or arguments), or none of them for "
            "single-host / pod auto-detect"
        )
    if given:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
        decision = "explicit"
    elif _looks_like_pod():
        try:
            jax.distributed.initialize()
        except Exception as e:
            raise RuntimeError(
                "this host looks like part of a multi-host pod "
                "(TPU_WORKER_HOSTNAMES / TPU_PROCESS_ADDRESSES / "
                "MEGASCALE_NUM_SLICES / KEYSTONE_POD env) but "
                "jax.distributed.initialize() failed — refusing to "
                "fall back to single-host mode, which would train a "
                "separate model per host"
            ) from e
        decision = "pod"
    else:
        decision = "single-host"
    _decision = decision
    if decision == "single-host":
        logger.info(
            "single host: no COORDINATOR_ADDRESS/NUM_PROCESSES/"
            "PROCESS_ID and no pod environment; jax.distributed not "
            "started, no network touched"
        )
    else:
        logger.info(
            "distributed runtime up (%s): process %d/%d, %d local / "
            "%d global devices",
            decision,
            jax.process_index(),
            jax.process_count(),
            jax.local_device_count(),
            jax.device_count(),
        )
    return decision


def multislice_shape(
    n_devices: int,
    n_slices: Optional[int] = None,
    n_model: int = 1,
) -> Tuple[int, int, int]:
    """Resolve the (dcn, data, model) mesh shape for ``n_devices``.

    ``n_slices`` defaults to the number of distinct slices the platform
    reports (1 when undetectable). ``n_model`` divides the per-slice
    device count; the remainder is the intra-slice data axis.
    """
    if n_slices is None:
        n_slices = _detect_num_slices()
    if n_devices % n_slices:
        raise ValueError(
            f"{n_devices} devices not divisible into {n_slices} slices"
        )
    per_slice = n_devices // n_slices
    if per_slice % n_model:
        raise ValueError(
            f"per-slice device count {per_slice} not divisible by "
            f"model axis {n_model}"
        )
    return n_slices, per_slice // n_model, n_model


def _detect_num_slices(devices: Optional[Sequence[jax.Device]] = None) -> int:
    devs = list(devices) if devices is not None else jax.devices()
    slice_ids = {getattr(d, "slice_index", 0) for d in devs}
    return max(len(slice_ids), 1)


def make_multislice_mesh(
    n_slices: Optional[int] = None,
    n_model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dcn, data, model) mesh.

    Devices are grouped so that the ``dcn`` axis exactly follows slice
    boundaries (each mesh row is one slice's devices) — DCN-crossing
    collectives then appear only on the ``dcn`` axis. Solvers that psum
    over the example axis shard data over ``("dcn", "data")`` jointly
    (mesh.data_sharding handles this), which XLA lowers to an
    ICI reduce(-scatter) per slice plus one small DCN all-reduce of the
    (b, b)-shaped partials — the treeReduce topology of the reference
    (MLMatrixUtils.treeReduce) realized in hardware.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n_slices_, n_data, n_model_ = multislice_shape(
        len(devs), n_slices if n_slices is not None
        else _detect_num_slices(devs),
        n_model,
    )
    # stable grouping: sort by (slice, process, id) so each dcn row is one
    # physical slice when slice metadata exists
    devs.sort(
        key=lambda d: (
            getattr(d, "slice_index", 0),
            getattr(d, "process_index", 0),
            d.id,
        )
    )
    arr = np.array(devs).reshape(n_slices_, n_data, n_model_)
    return Mesh(arr, (DCN_AXIS, DATA_AXIS, MODEL_AXIS))
