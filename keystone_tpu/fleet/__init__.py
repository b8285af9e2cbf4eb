"""Fleet tier: cross-host serving over N gateway processes.

PRs 1–5 built a complete single-host request plane (compiled bucketed
engines behind micro-batchers, shared-nothing ``EnginePool`` lanes,
admission control, one HTTP gateway). This package is the first
multi-process layer above it — the ``EnginePool`` topology lifted to
HTTP distance, where a replica is a whole ``serve-gateway`` process:

- ``ReplicaRegistry`` / ``Replica`` (registry.py): membership (static
  ``--replica`` URLs + ``POST /registerz`` self-registration),
  background ``/readyz`` health probes (burn-state body and the
  ``X-Keystone-Load`` header included), scraped load, and request-path
  health with half-open recovery mirroring ``Lane.healthy``.
- ``RouterServer`` (router.py): least-loaded routing with
  retry-once-on-another-replica, typed ``Overloaded`` propagation
  (429/504/503 semantics survive the extra hop), **SLO federation**
  (``/metrics`` merges every replica's scrape so ``le``-bucket
  quantiles are true fleet quantiles; ``/slz`` burns a fleet-wide
  latency SLO over the merged buckets), the ``/fleetz`` roster, and
  the ``router.replica.blackhole`` chaos point on the forward path.

CLI: ``python -m keystone_tpu serve-router --replica URL ...``;
drill: ``bin/smoke-fleet.sh``; tests: ``tests/fleet/``.
"""

from keystone_tpu.fleet.registry import Replica, ReplicaRegistry
from keystone_tpu.fleet.router import (
    ReplicaUnavailable,
    RouterMetrics,
    RouterServer,
)

__all__ = [
    "Replica",
    "ReplicaRegistry",
    "ReplicaUnavailable",
    "RouterMetrics",
    "RouterServer",
]
