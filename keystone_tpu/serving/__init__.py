"""Serving subsystem: the deployable half of the SURVEY §7 lowering.

A ``FittedPipeline`` is the trained artifact (the reference's
serializable ``FittedPipeline``); this package turns it into a
production inference engine:

- ``CompiledPipeline`` (engine.py): bucketed compiled execution —
  incoming batches are zero-padded up to a fixed set of row buckets so
  steady-state traffic compiles at most ``len(buckets)`` XLA programs,
  with input-buffer donation and an optional mesh-sharded variant.
  ``featurize=`` fuses a second fitted (pure-JAX) featurize pipeline
  in front of the model inside every bucket program — device-side
  featurization: raw uint8 staged (~4× fewer H2D bytes than f32
  features, counted by ``keystone_serving_h2d_bytes_total``), cast +
  featurize + predict in one dispatch. ``param_sharding=`` shards the
  MODEL over the mesh's model axis (see sharding.py below) — models
  bigger than one chip's HBM serve on the mesh.
- ``sharding.py``: the declarative model-sharding layer —
  ``match_partition_rules`` (regex over the fitted pipeline's named
  param pytree -> ``PartitionSpec`` tree), ``make_shard_fns`` /
  ``make_gather_fns`` placement callables, a default rule set for the
  repo's solver outputs (weight matrices split on the output axis,
  biases replicated), and the ``ParamBinder`` functionalization seam
  that turns params into sharded program arguments.
- ``MicroBatcher`` (batching.py): adaptive micro-batching — a
  thread-safe queue that coalesces single-example ``submit()`` requests
  into spec-homogeneous windows (interleaved request streams with
  different shapes each get their own) under a max-latency deadline,
  with a ``swap_engine()`` hook for zero-downtime engine replacement.
- ``LanePipeline`` (pipeline.py): the staged serving lane behind
  ``MicroBatcher(pipeline_depth=N)`` — host-prep (stack or a pluggable
  ``host_featurize`` items-mode hook + pad into a reusable host buffer
  pool), H2D upload, device compute, and deliver run on separate
  threads behind bounded handoff queues, so one window's host work
  overlaps the previous window's device compute. Bit-identical to
  serial dispatch; per-stage spans/metrics with streaming-bench-style
  bottleneck attribution.
- ``ServingMetrics`` (metrics.py): per-bucket compile/dispatch counts,
  request-size histogram, queue depth, p50/p95/p99 latency, windowed
  examples/sec — auto-registered into the process-global
  ``MetricsRegistry`` (``keystone_tpu.observability``) so the admin
  endpoint's ``/metrics`` scrapes every live engine.
- ``suggest_buckets`` (autoscale.py): propose the k-bucket set that
  minimizes expected padding waste over the observed request-size
  histogram (the metrics-driven replacement for operator-chosen
  buckets).

Persistent-compile-cache setup lives in
``keystone_tpu.parallel.runtime.setup_compilation_cache`` (a restarted
server warms from disk instead of recompiling): the package's one
executable cache. The request plane in
FRONT of these engines — admission control, replica lanes, live
re-bucketing, HTTP — is ``keystone_tpu.gateway``.
"""

from keystone_tpu.serving.autoscale import padding_waste, suggest_buckets
from keystone_tpu.serving.batching import MicroBatcher
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.serving.metrics import ServingMetrics
from keystone_tpu.serving.pipeline import (
    HostBufferPool,
    HostFeaturize,
    LanePipeline,
)
from keystone_tpu.serving.sharding import (
    DEFAULT_RULES,
    make_gather_fns,
    make_shard_fns,
    match_partition_rules,
    named_params,
)

__all__ = [
    "CompiledPipeline",
    "DEFAULT_RULES",
    "HostBufferPool",
    "HostFeaturize",
    "LanePipeline",
    "MicroBatcher",
    "ServingMetrics",
    "make_gather_fns",
    "make_shard_fns",
    "match_partition_rules",
    "named_params",
    "padding_waste",
    "suggest_buckets",
]
