"""Cross-model featurize CSE: compute shared prefixes once per window.

KeystoneML's rule engine deduplicates common subexpressions across a
training DAG; the serving-plane analogue is co-hosted models whose
fused featurize chains are the SAME chain. Detection is by content,
not by name: two models share a prefix iff their featurize pipelines'
``featurize_token``s — the SHA-256 digest of operator classes, wiring,
and every parameter array — are equal (``featurize_groups``), the
proof two prefixes compute the same function.

``SharedPrefixEngine`` then hosts one whole group behind one engine:
a single per-bucket XLA program computes ``feat = featurize(raw)``
ONCE and fans the activations out to every member's head —

    {model_a: head_a(feat), model_b: head_b(feat), ...}

Dict outputs ride the existing window plumbing untouched: the
``MicroBatcher`` tree-slices each row out of the batched output, so
every request's future resolves to a per-model dict and the zoo picks
(or fans out) from it. The engine's own compile/dispatch counters are
the measurement seam ``tests/zoo/test_cse.py`` gates on: one
trace per bucket and one dispatch per window for the whole group,
where solo hosting pays one of each PER MODEL.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from keystone_tpu.observability import device as device_obs
from keystone_tpu.observability.attribution import RowClaimQueue
from keystone_tpu.serving.engine import CompiledPipeline
from keystone_tpu.serving.featurize import featurize_token

logger = logging.getLogger(__name__)


def featurize_groups(
    featurizers: Dict[str, Any]
) -> List[Tuple[str, ...]]:
    """Group model ids by identical ``featurize_token``.
    ``featurizers`` maps model id -> fitted featurize pipeline (models
    without one simply aren't candidates — pass only those that have
    one). Returns sorted id tuples, groups of one included: the caller
    decides that only len >= 2 groups earn a shared engine."""
    by_token: Dict[str, List[str]] = {}
    for model_id in sorted(featurizers):
        fitted = featurizers[model_id]
        try:
            token = featurize_token(fitted)
        except Exception:
            # an unfingerprintable chain can't PROVE it equals another,
            # so it never shares
            logger.info(
                "cse: featurize of %s not fingerprintable; hosting "
                "solo", model_id, exc_info=True,
            )
            token = f"_unhashable:{model_id}"
        by_token.setdefault(token, []).append(model_id)
    return sorted(
        tuple(ids) for ids in by_token.values()
    )


class SharedPrefixEngine(CompiledPipeline):
    """One engine serving a whole CSE group. ``heads`` maps model id
    -> fitted head pipeline; ``featurize`` is the group's (verified
    identical) fused prefix. Outputs are dicts keyed by model id, one
    entry per head, from one fused program per bucket."""

    def __init__(
        self,
        featurize,
        heads: Dict[str, Any],
        buckets: Sequence[int],
        **kwargs,
    ):
        if featurize is None:
            raise ValueError(
                "SharedPrefixEngine needs the shared featurize prefix"
            )
        if len(heads) < 1:
            raise ValueError("need at least one head")
        # deterministic head order: the traced program's output dict
        # (and therefore its cost model and its compile-cache key) must
        # not depend on dict insertion order at the call site
        self.heads = {mid: heads[mid] for mid in sorted(heads)}
        # param sharding binds ONE pipeline's params; the multi-head
        # program would need a per-head binder — host sharded models
        # solo instead of silently sharding only the primary head
        if kwargs.get("param_sharding"):
            raise ValueError(
                "SharedPrefixEngine does not compose with "
                "param_sharding; host sharded models solo"
            )
        super().__init__(
            pipeline=next(iter(self.heads.values())),
            buckets=buckets,
            featurize=featurize,
            **kwargs,
        )
        # -- per-model attribution inputs (observability/attribution) --
        # row claims enqueued at submit time (by the zoo, or directly
        # when the engine is driven standalone), drained FIFO per
        # dispatched window; the zoo replaces this with a UNIT-level
        # queue shared across lanes
        self.claims = RowClaimQueue()
        # bucket -> (prefix_flops, {model: head_flops}): the fair-split
        # cost inputs, extracted best-effort at warmup
        self._split_costs: Dict[int, Tuple[float, Dict[str, float]]] = {}

    # -- attribution seams -------------------------------------------------

    def claim_rows(self, model_id: str, rows: float) -> None:
        """Declare that ``rows`` of upcoming window traffic belong to
        ``model_id``."""
        self.claims.claim(model_id, rows)

    def drain_claims(self, n_valid: float) -> Dict[str, float]:
        """Consume claims covering ``n_valid`` dispatched rows ->
        ``{model: rows}`` (see ``RowClaimQueue.drain``)."""
        return self.claims.drain(n_valid)

    def split_cost_model(
        self, bucket: int
    ) -> Optional[Tuple[float, Dict[str, float]]]:
        """``(prefix_flops, {model: head_flops})`` for one bucket
        program, or None where extraction failed (the binding degrades
        to pure row-share splitting)."""
        return self._split_costs.get(bucket)

    def _register_cost_model(self, bucket: int, fn, staged) -> None:
        """On top of the whole-program cost model, extract the SPLIT
        one: the shared prefix lowered alone vs each head lowered over
        the prefix's output aval. Same best-effort contract — a backend
        reporting nothing leaves the split absent and attribution
        degrades to row share."""
        super()._register_cost_model(bucket, fn, staged)
        try:
            feat_run = self.featurize._batch_run
            prefix_model = device_obs.compiled_cost_model(
                jax.jit(feat_run).lower(staged)
            )
            prefix_flops = float(prefix_model.get("flops") or 0.0)
            feat_aval = jax.eval_shape(feat_run, staged)
            head_flops: Dict[str, float] = {}
            for mid, head in self.heads.items():
                head_model = device_obs.compiled_cost_model(
                    jax.jit(head._batch_run).lower(feat_aval)
                )
                head_flops[mid] = float(head_model.get("flops") or 0.0)
            if prefix_flops > 0 and any(head_flops.values()):
                self._split_costs[bucket] = (prefix_flops, head_flops)
        except Exception:
            logger.debug(
                "no split cost model for shared bucket %d", bucket,
                exc_info=True,
            )

    def _make_jit(self, bucket: int):
        feat_run = self.featurize._batch_run
        runs = {
            mid: head._batch_run for mid, head in self.heads.items()
        }
        metrics = self.metrics

        def staged(arr):
            # one trace-count per XLA compile of the whole group's
            # program — the tests' compile-counter gate reads this
            metrics.record_trace(bucket)
            feat = feat_run(arr)
            # the shared prefix is computed ONCE; every head consumes
            # the same activations inside the same program, so XLA can
            # fuse across all head boundaries too
            return {mid: run(feat) for mid, run in runs.items()}

        return jax.jit(
            staged, donate_argnums=(0,) if self.donate else ()
        )


__all__ = ["SharedPrefixEngine", "featurize_groups"]
