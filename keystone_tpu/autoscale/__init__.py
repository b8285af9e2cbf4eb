"""Autonomous fleet elasticity: the loop nobody was closing.

PRs 8–11 built every primitive this package needs — the shared AOT
executable store makes a fresh replica ~5× cheaper to start, the
``--register``/``{"listening": ...}`` handshake makes one spawnable
and routable without port races, the router's federated ``/metrics``
+ ``/slz`` say how the FLEET is doing, and the per-request phase
decomposition says *where* latency goes. This package is the
controller over all of it:

- ``supervisor.py`` — replica processes as a managed set: spawn
  ``serve-gateway`` subprocesses (or in-process replicas for the
  capacity planner), retire through the graceful
  deregister → drain → exit protocol, replace the dead.
- ``policy.py`` — the pure decision engine: SLO burn + fleet p99 +
  per-replica load + phase attribution (scale out only when
  ``queue_wait`` dominates — ``device``-bound latency vetoes, more
  replicas wouldn't help), with hysteresis, per-direction cooldowns,
  min/max bounds, and a scale-down ban while any replica is
  half-open.
- ``controller.py`` — the tick: scrape, decide, converge; every
  decision a structured event + ``keystone_autoscale_*`` series +
  an ``autoscale.decision`` span.
- ``planner.py`` — ``serve-capacity-plan``: replay the recorded peak
  ×1..×N against 1..K replicas, fit replicas-vs-offered-load, derive
  the policy thresholds — measured, not guessed.
- ``cli.py`` — ``serve-autoscale``: router + supervisor + loop in
  one command.

CLI: ``python -m keystone_tpu serve-autoscale --slo-latency-ms 250``;
drill: ``bin/smoke-autoscale.sh``; tests: ``tests/autoscale/``.
"""

from keystone_tpu.autoscale.policy import (
    Decision,
    FleetObservation,
    PolicyConfig,
    PolicyEngine,
    phase_shares,
)
from keystone_tpu.autoscale.supervisor import (
    InprocLauncher,
    SubprocessLauncher,
    Supervisor,
    deregister_replica,
)

__all__ = [
    "Decision",
    "FleetObservation",
    "InprocLauncher",
    "PolicyConfig",
    "PolicyEngine",
    "SubprocessLauncher",
    "Supervisor",
    "deregister_replica",
    "phase_shares",
]
