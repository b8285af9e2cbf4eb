"""Trace-driven load generation + chaos harness.

The experiment DRIVER the serving stack was missing: PRs 1–6 built
admission control, replica lanes, pipelined dispatch, SLO forensics,
and device-truth observability — all *observers*; this package
generates the traffic and the failures they observe, then asserts the
stack's invariants held:

- ``trace`` — parse the gateway's ``--request-log`` JSONL into
  replayable events; synthesize open-loop workloads (Poisson /
  heavy-tail lognormal / Pareto arrivals, request-size mixtures,
  deadline distributions).
- ``runner`` — MLPerf-LoadGen-style open-loop replay against a live
  gateway (HTTP or in-process), preserving recorded inter-arrival
  gaps with a ``--speed`` factor, arming a chaos timeline as it runs.
- ``faults`` — the process-global ``FaultInjector``: named fault
  points compiled into the hot paths as default-off no-ops
  (``gateway.lane.kill``, ``pipeline.host_prep.stall``,
  ``engine.dispatch.error``, ``otlp.export.blackhole``,
  ``gateway.swap.force``), armable via code, ``KEYSTONE_FAULTS`` env,
  or ``POST /chaosz``.
- ``invariants`` — the verdict: every admitted request resolves,
  failures are typed sheds only, readiness and p99 recover after the
  fault clears, shed rate stays in bounds.

``python -m keystone_tpu serve-loadgen`` is the CLI
(``loadgen/cli.py``); ``tests/loadgen/`` and ``bin/smoke-chaos.sh``
drive the same APIs.

Import weight: the serving hot paths (``gateway/pool.py``,
``serving/engine.py``, ``serving/pipeline.py``,
``observability/otlp.py``) import this package for ``faults`` alone,
so only ``faults`` loads eagerly — the driver half (trace parsing,
the runner, the checker, the CLI) resolves lazily via module
``__getattr__`` and never rides along into a serving process that
doesn't use it.
"""

from keystone_tpu.loadgen import faults
from keystone_tpu.loadgen.faults import (
    FAULT_POINTS,
    FaultInjected,
    FaultInjector,
    FaultSpec,
)

# lazy attribute -> owning submodule (the driver half of the package)
_LAZY = {
    "trace": None,
    "runner": None,
    "invariants": None,
    "cli": None,
    "TraceEvent": "trace",
    "collapse_posts": "trace",
    "load_trace": "trace",
    "parse_request_log": "trace",
    "synthesize": "trace",
    "FaultPlan": "runner",
    "HttpTarget": "runner",
    "InprocTarget": "runner",
    "LoadGenerator": "runner",
    "LoadReport": "runner",
    "RequestRecord": "runner",
    "InvariantChecker": "invariants",
    "InvariantResult": "invariants",
    "Verdict": "invariants",
}

__all__ = sorted(
    ["FAULT_POINTS", "FaultInjected", "FaultInjector", "FaultSpec",
     "faults"] + list(_LAZY)
)


def __getattr__(name):
    target = _LAZY.get(name, "missing")
    if target == "missing":
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        )
    import importlib

    module = importlib.import_module(
        f"keystone_tpu.loadgen.{target or name}"
    )
    return module if target is None else getattr(module, name)
