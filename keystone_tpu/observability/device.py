"""Device truth: what the chip is, what it peaks at, what it holds.

Everything else in the observability plane measures *host wall time*;
this module is the device-side half of the cost model the ROADMAP's
"as fast as the hardware allows" needs:

- **Detection** (``device_table``): the local device set — kind,
  platform, count, peak dense FLOP/s and HBM bandwidth from a
  per-device-kind table (overridable via ``KEYSTONE_PEAK_FLOPS`` /
  ``KEYSTONE_PEAK_MEMBW_GBPS`` for hardware the table doesn't know),
  and the HBM byte limit where the runtime reports one. Computed ONCE
  — ``jax.devices()`` can trigger full backend init, a cost no
  ``/metrics`` scrape should ever pay — and exported as the standard
  constant-1 ``keystone_device_info`` gauge.
- **Cost-model extraction** (``compiled_cost_model``): normalize
  ``jax.jit(...).lower().compile().cost_analysis()`` (a dict, a
  list-wrapped dict, or None depending on backend) and
  ``memory_analysis()`` into one flat ``{flops, bytes_accessed,
  temp_bytes, ...}`` dict. Best-effort by contract: a backend that
  reports nothing yields ``{}``, never an exception — the CPU CI
  degrades to *absent* series, not zeros.
- **Memory telemetry** (``device_memory_stats``,
  ``DeviceMemorySampler``): THE one None-guarded ``memory_stats()``
  probe (``ops/learning/weighted_ls.py`` and ``workflow/auto_cache.py``
  route through it instead of hand-rolling their own), plus a sampler
  thread publishing per-device in-use / peak / limit gauges on the
  registry. CPU backends report no device stats; the sampler falls
  back to one host-RAM series (``device="host"``) so a CPU deployment
  still has a memory surface.

``ServingMetrics`` combines the peaks with each engine's per-bucket
compiled cost model into the rolling MFU gauge and the
compute-vs-bandwidth roofline classification (serving/metrics.py).
"""

from __future__ import annotations

import logging
import os
import re
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

# Peak DENSE matmul throughput per chip (bf16/fp16 where the part has
# it, else f32) and peak HBM bandwidth, keyed by a case-insensitive
# word-bounded substring of ``device.device_kind``. First match wins,
# most specific entries first; the word boundary keeps "l4" from
# claiming an L40S (unknown parts stay (None, None) — absent series
# beat fabricated peaks). Vendor datasheet numbers — the MFU
# denominator, same convention as the PaLM MFU reports (model FLOPs
# over peak FLOPs).
PEAK_TABLE: Tuple[Tuple[str, float, float], ...] = (
    # (kind substring, peak FLOP/s, peak HBM bytes/s)
    ("tpu v6e", 918e12, 1640e9),     # Trillium; some runtimes say "v6e"
    ("tpu v6", 918e12, 1640e9),      # ... others "TPU v6 lite"
    ("tpu v5p", 459e12, 2765e9),
    ("tpu v5 lite", 197e12, 819e9),  # v5e reports "TPU v5 lite"
    ("tpu v5e", 197e12, 819e9),
    ("tpu v5", 459e12, 2765e9),
    ("tpu v4", 275e12, 1200e9),
    ("tpu v3", 123e12, 900e9),
    ("tpu v2", 45e12, 700e9),
    ("h200", 989e12, 4800e9),
    ("h100", 989e12, 3350e9),
    ("a100", 312e12, 2039e9),
    ("l4", 121e12, 300e9),
    ("v100", 125e12, 900e9),
    ("t4", 65e12, 320e9),
)

_ENV_PEAK_FLOPS = "KEYSTONE_PEAK_FLOPS"
_ENV_PEAK_MEMBW = "KEYSTONE_PEAK_MEMBW_GBPS"


def peaks_for(device_kind: Optional[str]) -> Tuple[Optional[float], Optional[float]]:
    """``(peak_flops, peak_membw_bytes_per_s)`` for a device kind, from
    the env overrides first, then the table; ``(None, None)`` for
    hardware neither knows (MFU/roofline series stay absent)."""
    flops = membw = None
    env_flops = os.environ.get(_ENV_PEAK_FLOPS)
    if env_flops:
        try:
            flops = float(env_flops)
        except ValueError:
            logger.warning("ignoring non-numeric %s=%r",
                           _ENV_PEAK_FLOPS, env_flops)
    env_membw = os.environ.get(_ENV_PEAK_MEMBW)
    if env_membw:
        try:
            membw = float(env_membw) * 1e9
        except ValueError:
            logger.warning("ignoring non-numeric %s=%r",
                           _ENV_PEAK_MEMBW, env_membw)
    if flops is not None and membw is not None:
        return flops, membw
    kind = (device_kind or "").lower()
    for sub, table_flops, table_membw in PEAK_TABLE:
        if re.search(rf"\b{re.escape(sub)}\b", kind):
            return (flops if flops is not None else table_flops,
                    membw if membw is not None else table_membw)
    return flops, membw


def device_memory_stats(device: Any = None) -> Optional[Dict[str, int]]:
    """THE ``memory_stats()`` probe: one code path, one None-guard.
    Returns the runtime's stats dict (``bytes_in_use`` /
    ``peak_bytes_in_use`` / ``bytes_limit`` where the backend reports
    them) or None — backends without stats (CPU) and uninitializable
    backends both land on None, never an exception."""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    return stats or None


def host_memory_stats() -> Optional[Dict[str, int]]:
    """Host-RAM analogue of ``device_memory_stats`` for backends with
    no device allocator stats: limit = MemTotal, in-use derived from
    MemAvailable, peak = this process's max RSS."""
    stats: Dict[str, int] = {}
    try:
        with open("/proc/meminfo") as f:
            fields = {}
            for line in f:
                parts = line.split()
                if parts and parts[0].rstrip(":") in (
                    "MemTotal", "MemAvailable"
                ):
                    fields[parts[0].rstrip(":")] = int(parts[1]) * 1024
        if "MemTotal" in fields:
            stats["bytes_limit"] = fields["MemTotal"]
            if "MemAvailable" in fields:
                stats["bytes_in_use"] = (
                    fields["MemTotal"] - fields["MemAvailable"]
                )
    except OSError:
        pass
    try:
        import resource

        # ru_maxrss is kilobytes on Linux but bytes on macOS
        scale = 1 if sys.platform == "darwin" else 1024
        stats["peak_bytes_in_use"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * scale
        )
    except Exception:
        pass
    return stats or None


# -- the one-time detected device table ------------------------------------

_table: Optional[List[Dict[str, Any]]] = None
_table_lock = threading.Lock()


def device_table() -> List[Dict[str, Any]]:
    """The local device set as one row per device KIND (kind, platform,
    count, peak FLOP/s, peak HBM bandwidth, HBM byte limit). Computed
    once — ``jax.devices()`` may initialize the whole backend, which a
    per-scrape path must never pay — and safe on hosts where the
    backend fails to init (empty table)."""
    global _table
    with _table_lock:
        if _table is not None:
            return [dict(row) for row in _table]
        rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
        try:
            import jax

            for dev in jax.devices():
                key = (dev.device_kind, dev.platform)
                row = rows.get(key)
                if row is None:
                    flops, membw = peaks_for(dev.device_kind)
                    stats = device_memory_stats(dev)
                    row = rows[key] = {
                        "kind": dev.device_kind,
                        "platform": dev.platform,
                        "count": 0,
                        "peak_flops": flops,
                        "peak_membw_bytes_per_s": membw,
                        "hbm_bytes_limit": (
                            stats.get("bytes_limit") if stats else None
                        ),
                    }
                row["count"] += 1
        except Exception:
            logger.exception("device detection failed; empty table")
        _table = list(rows.values())
        return [dict(row) for row in _table]


def reset_device_table() -> None:
    """Drop the cached table (tests monkeypatching the backend)."""
    global _table
    with _table_lock:
        _table = None


_ENV_CHIP_HBM = "KEYSTONE_CHIP_HBM_BYTES"


def chip_hbm_bytes() -> Optional[int]:
    """The per-chip parameter budget the zoo placement optimizer plans
    against: ``$KEYSTONE_CHIP_HBM_BYTES`` when set (CPU CI and hosts
    whose allocator reports no limit), else the smallest
    ``hbm_bytes_limit`` the runtime reports across device kinds (a
    heterogeneous host must plan for its tightest chip). None when
    neither source knows — callers then skip budget-driven decisions
    rather than plan against a fabricated number."""
    env = os.environ.get(_ENV_CHIP_HBM)
    if env:
        try:
            return int(float(env))
        except ValueError:
            logger.warning("ignoring unparseable %s=%r",
                           _ENV_CHIP_HBM, env)
    limits = [
        row["hbm_bytes_limit"] for row in device_table()
        if row.get("hbm_bytes_limit")
    ]
    return min(limits) if limits else None


def register_device_metrics(registry) -> None:
    """Export the detected table as the standard constant-1 info gauge:
    ``keystone_device_info{kind, platform, count, peak_flops}``.
    Table detection is the one-time cost; every scrape reads the
    cache."""
    def cells():
        return {
            (
                row["kind"],
                row["platform"],
                str(row["count"]),
                str(row["peak_flops"] or "unknown"),
            ): 1.0
            for row in device_table()
        }

    registry.gauge_func(
        "keystone_device_info",
        cells,
        "constant 1 labeled with the detected device kind/count/peaks",
        ("kind", "platform", "count", "peak_flops"),
    )


# -- compiled-program cost extraction --------------------------------------

# cost_analysis keys -> our flat names
_COST_KEYS = (("flops", "flops"), ("bytes accessed", "bytes_accessed"),
              ("transcendentals", "transcendentals"))
_MEMORY_ATTRS = (
    ("temp_size_in_bytes", "temp_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
)


def compiled_cost_model(compiled: Any) -> Dict[str, float]:
    """Normalize one XLA program's analyses into a flat ``{flops,
    bytes_accessed, temp_bytes, ...}`` dict. Accepts a
    ``jax.stages.Lowered`` (``cost_analysis`` without paying an XLA
    compile; no ``memory_analysis``) or a ``Compiled`` (both).
    Backends differ: ``cost_analysis()`` is a dict, a list-wrapped
    dict, or None/raising — any shape that carries nothing yields
    ``{}`` (absent series, the graceful-degradation contract), never
    an exception."""
    model: Dict[str, float] = {}
    cost = None
    try:
        cost = compiled.cost_analysis()
    except Exception:
        cost = None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if isinstance(cost, dict):
        for src, dst in _COST_KEYS:
            v = cost.get(src)
            if isinstance(v, (int, float)) and v >= 0:
                model[dst] = float(v)
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for attr, dst in _MEMORY_ATTRS:
            v = getattr(mem, attr, None)
            if isinstance(v, (int, float)) and v >= 0:
                model[dst] = float(v)
    return model


# -- the memory sampler thread ---------------------------------------------

# the memory_stats keys the sampler exports, as their gauge `stat` label
_SAMPLED_STATS = (
    ("bytes_in_use", "in_use"),
    ("peak_bytes_in_use", "peak"),
    ("bytes_limit", "limit"),
)


class DeviceMemorySampler:
    """Background thread publishing ``device.memory_stats()`` as
    ``keystone_device_memory_bytes{device, kind, stat}`` gauges.

    Devices without allocator stats contribute no series (absent, not
    zero); when NO device reports stats and the platform is CPU, one
    host-RAM series set (``device="host"``, ``kind="host-ram"``)
    publishes instead so a CPU deployment still has a memory surface.
    ``sample_once()`` is the unit-testable core; ``start()`` samples
    immediately, then every ``interval_s`` on a daemon thread."""

    def __init__(
        self,
        registry=None,
        interval_s: float = 10.0,
        devices: Optional[Sequence[Any]] = None,
    ):
        from keystone_tpu.observability.registry import get_global_registry

        self.registry = (
            registry if registry is not None else get_global_registry()
        )
        self.interval_s = float(interval_s)
        self._devices = devices
        self._gauge = self.registry.gauge(
            "keystone_device_memory_bytes",
            "device allocator memory (absent on backends without "
            "stats; device=\"host\" rows are host RAM)",
            ("device", "kind", "stat"),
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _device_list(self) -> Sequence[Any]:
        if self._devices is not None:
            return self._devices
        try:
            import jax

            return jax.devices()
        except Exception:
            return ()

    def sample_once(self) -> int:
        """Publish one sample of every device; returns the number of
        device series sets written (0 = no device reported stats)."""
        published = 0
        devices = self._device_list()
        # an EMPTY device list (backend failed to init) must stay an
        # absent family, not masquerade as a healthy CPU host
        all_cpu = bool(devices)
        for i, dev in enumerate(devices):
            if getattr(dev, "platform", None) != "cpu":
                all_cpu = False
            stats = device_memory_stats(dev)
            if not stats:
                continue
            published += 1
            kind = getattr(dev, "device_kind", "unknown")
            for key, stat in _SAMPLED_STATS:
                if key in stats:
                    self._gauge.set(
                        float(stats[key]), (str(i), kind, stat)
                    )
        if not published and all_cpu:
            host = host_memory_stats()
            if host:
                for key, stat in _SAMPLED_STATS:
                    if key in host:
                        self._gauge.set(
                            float(host[key]), ("host", "host-ram", stat)
                        )
        return published

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:
                logger.exception("device memory sample failed")

    def start(self) -> "DeviceMemorySampler":
        if self._thread is not None:
            return self
        self._stop.clear()  # restartable (server stop/start cycles)
        try:
            self.sample_once()
        except Exception:
            logger.exception("initial device memory sample failed")
        self._thread = threading.Thread(
            target=self._loop, name="keystone-device-memory", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


# Refcounted per-registry sampler sharing: an admin endpoint and a
# gateway frontend in one process both want the memory families on the
# (usually shared) global registry — one sampler thread per registry,
# not one per server.
_samplers_lock = threading.Lock()
_samplers: Dict[int, List] = {}  # id(registry) -> [sampler, refcount]


def acquire_memory_sampler(
    registry=None, interval_s: float = 10.0
) -> DeviceMemorySampler:
    """Start (or share) the memory sampler for a registry. Each
    ``acquire`` must be paired with one ``release_memory_sampler`` —
    the underlying thread stops when the last holder releases. When the
    registry already has a sampler, the tightest requested interval
    wins (the loop re-reads ``interval_s`` every wait)."""
    from keystone_tpu.observability.registry import get_global_registry

    registry = registry if registry is not None else get_global_registry()
    with _samplers_lock:
        entry = _samplers.get(id(registry))
        if entry is None:
            entry = _samplers[id(registry)] = [
                DeviceMemorySampler(
                    registry=registry, interval_s=interval_s
                ).start(),
                0,
            ]
        elif interval_s < entry[0].interval_s:
            entry[0].interval_s = float(interval_s)
        entry[1] += 1
        return entry[0]


def release_memory_sampler(sampler: DeviceMemorySampler) -> None:
    with _samplers_lock:
        entry = _samplers.get(id(sampler.registry))
        if entry is None or entry[0] is not sampler:
            sampler.stop()  # not shared (constructed directly)
            return
        entry[1] -= 1
        if entry[1] <= 0:
            del _samplers[id(sampler.registry)]
            sampler.stop()


class MemorySamplerHost:
    """Mixin for endpoint servers with a ``registry``: hold the shared
    per-registry memory sampler between ``_start_memory_sampler()``
    (call after the server comes up) and ``_stop_memory_sampler()``
    (call before it goes down). Both are idempotent."""

    _mem_sampler: Optional[DeviceMemorySampler] = None

    def _start_memory_sampler(self) -> None:
        if self._mem_sampler is None:
            self._mem_sampler = acquire_memory_sampler(
                registry=self.registry
            )

    def _stop_memory_sampler(self) -> None:
        if self._mem_sampler is not None:
            release_memory_sampler(self._mem_sampler)
            self._mem_sampler = None


__all__ = [
    "DeviceMemorySampler",
    "MemorySamplerHost",
    "acquire_memory_sampler",
    "compiled_cost_model",
    "device_memory_stats",
    "device_table",
    "host_memory_stats",
    "peaks_for",
    "register_device_metrics",
    "release_memory_sampler",
    "reset_device_table",
]
