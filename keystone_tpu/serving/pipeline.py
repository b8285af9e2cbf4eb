"""Staged lane pipeline: overlap host prep, H2D upload, and device
compute behind one ``MicroBatcher``.

The serving-side analogue of the streaming loader's
decode/upload/compute overlap
(``loaders/streaming.py: featurized_batches``): a serial batcher lane runs coalesce → stack → pad → device_put →
compute → deliver one window at a time, so while the device runs window
k, window k+1's host work and H2D transfer sit idle in the queue. Here
the dispatch is split into explicit stages connected by BOUNDED handoff
queues (depth ~2), each stage on its own thread:

    coalesce ──▶ host-prep ──▶ upload ──▶ compute ──▶ deliver
    (batcher     stack or       device_put  compiled    slice valid
     window      host-featurize + H2D sync  bucket fn   rows, resolve
     logic)      + pad into     (buffer     + ready     futures
                 pooled buffer  rides on)   sync (frees
                                            pool buffer)

so window k+1's host-prep and upload overlap window k's device compute.
When a queue fills, the coalesce thread blocks, pending requests pile
up behind the batcher, lane load rises, and the gateway's admission
controller sheds — backpressure is end-to-end, never an unbounded pile.

**Host featurize** is the pluggable prep hook: a callable turning one
coalesced window of RAW examples (any pytree — or non-array items like
strings) into the batched array tree the engine stages. Items-mode /
tokenizer front-ends (the text path's ``FusedTextHashTF``-style fused
featurizers) run behind the engine this way: clients submit raw items,
the featurize stage burns host cores while the device computes the
previous window. The same hook drives the serial path, so pipelined
and serial results stay comparable (and bit-identical — both modes
compose the engine's own stage primitives over identical values).

**Buffer pool**: host-prep writes each padded window into a small
per-(bucket, spec) pool of reusable host staging buffers (double
buffered — ``depth + 1`` per key), so steady-state windows allocate no
host memory. A buffer returns to the pool only once its window's
COMPUTE output is ready — backends may stage host arrays zero-copy
(the CPU backend does), so the first point the staged input is
provably consumed is the execution that read it, not the device_put's
own ready signal. The uploaded device buffers are engine-private and
feed the compiled program's donated arguments on backends with
donation support. ``reset()`` (engine swap) bumps the
pool generation: in-flight windows finish on their old engine and
their buffers — possibly sized for retired buckets — are dropped
instead of re-pooled.

Each stage opens a tracer span (``pipeline.host_prep`` / ``.upload`` /
``.compute`` / ``.deliver``) parented under the window's
``microbatch.coalesce`` span, and records per-stage seconds +
queue-depth series on the window's engine ``ServingMetrics``; the
derived per-lane ``bottleneck`` attribution and ``overlap_efficiency``
mirror the streaming bench's model (see ``ServingMetrics.bottleneck``).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from keystone_tpu.loadgen import faults
from keystone_tpu.observability.tracing import get_tracer

logger = logging.getLogger(__name__)

DEFAULT_DEPTH = 2

# HostFeaturize(raw examples of one window) -> batched pytree of arrays
# with a leading axis of len(examples). Runs on the host-prep thread;
# must be thread-safe and pure (same window -> same values).
HostFeaturize = Callable[[List[Any]], Any]

_SENTINEL = object()


class HostBufferPool:
    """Reusable padded host staging buffers, keyed by
    ``(bucket, treedef, per-leaf row shape/dtype)``.

    ``acquire`` hands out a free buffer tree or allocates one
    (``allocations`` counts these — the no-growth test reads it);
    ``release`` returns it unless the pool already holds
    ``max_per_key`` for that key or the pool generation moved on (an
    engine swap retired the bucket set the buffer was cut for)."""

    def __init__(self, max_per_key: int = DEFAULT_DEPTH + 1):
        self.max_per_key = max_per_key
        self.generation = 0  # guarded-by: _lock
        self.allocations = 0  # guarded-by: _lock
        self._free: Dict[Any, List[Any]] = {}  # guarded-by: _lock
        # live staging footprint: bytes sitting free in the pool +
        # bytes riding in-flight windows (the
        # ``keystone_serving_staging_bytes`` gauge input)
        self._pooled_bytes = 0  # guarded-by: _lock
        self._outstanding_bytes = 0  # guarded-by: _lock
        # a key pins (bucket, treedef, shapes, dtypes), so its buffer
        # size is a constant — computed once per key, not per window
        self._key_bytes: Dict[Any, int] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    @staticmethod
    def _tree_bytes(buffers: Any) -> int:
        return sum(
            int(getattr(a, "nbytes", 0))
            for a in jax.tree_util.tree_leaves(buffers)
        )

    def _bytes_for_locked(self, key: Any, buffers: Any) -> int:
        """Cached per-key buffer size (the ``_locked`` suffix is the
        caller-holds-``self._lock`` convention the guarded-by lint
        rule recognizes)."""
        nbytes = self._key_bytes.get(key)
        if nbytes is None:
            nbytes = self._key_bytes[key] = self._tree_bytes(buffers)
        return nbytes

    @property
    def staging_bytes(self) -> int:
        """Total host bytes the pool currently accounts for (pooled
        free buffers + buffers riding in-flight windows)."""
        with self._lock:
            return self._pooled_bytes + self._outstanding_bytes

    def reset(self) -> None:
        """Engine swap: drop every pooled buffer and invalidate
        outstanding ones (their release becomes a no-op drop)."""
        with self._lock:
            self.generation += 1
            self._free.clear()
            self._key_bytes.clear()  # keys are cut per bucket set
            # old-generation buffers still in flight stop being
            # accounted here — their release is a drop, not a return
            self._pooled_bytes = 0
            self._outstanding_bytes = 0

    def acquire(
        self, key: Any, alloc: Callable[[], Any]
    ) -> Tuple[int, Any]:
        with self._lock:
            free = self._free.get(key)
            if free:
                buffers = free.pop()
                nbytes = self._bytes_for_locked(key, buffers)
                self._pooled_bytes -= nbytes
                self._outstanding_bytes += nbytes
                return self.generation, buffers
            self.allocations += 1
            gen = self.generation
        buffers = alloc()
        with self._lock:
            if gen == self.generation:
                self._outstanding_bytes += self._bytes_for_locked(
                    key, buffers
                )
        return gen, buffers

    def publish_staging_bytes(self, resolve_metrics: Callable[[], Any]) -> None:
        """Stamp the live footprint on ``resolve_metrics()``'s gauge,
        atomically with ``reset()``: a swap reassigns the batcher's
        current metrics BEFORE it resets this pool, and re-stamps both
        gauges AFTER, so a stage thread that selects its target and
        publishes while holding this lock can never leave a retired
        engine carrying the new pool's bytes."""
        with self._lock:
            resolve_metrics().set_staging_bytes(
                self._pooled_bytes + self._outstanding_bytes
            )

    def release(self, key: Any, generation: int, buffers: Any) -> None:
        if buffers is None:
            return  # window died before its buffers were attached
        with self._lock:
            if generation != self.generation:
                # cut for a retired engine's buckets: drop (reset()
                # already zeroed their outstanding-byte accounting)
                return
            nbytes = self._bytes_for_locked(key, buffers)
            self._outstanding_bytes -= nbytes
            free = self._free.setdefault(key, [])
            if len(free) < self.max_per_key:
                free.append(buffers)
                self._pooled_bytes += nbytes


def resolve_window_futures(metrics, valid, futures, enqueued) -> None:
    """Deliver one window: gather ``valid`` (a tree of valid-rows
    outputs) to host numpy ONCE, resolve each future with a row VIEW of
    it, and record the completion-timed per-request latency. Shared by
    the serial batcher dispatch and the pipelined deliver stage so the
    two delivery paths cannot drift — per-row jax.Array slicing here
    would dispatch one device op per request (GIL-heavy; measured as
    the pipelined lane's bottleneck before the single host gather)."""
    valid = jax.tree_util.tree_map(np.asarray, valid)
    done = time.perf_counter()
    for i, fut in enumerate(futures):
        row = jax.tree_util.tree_map(lambda a, i=i: a[i], valid)
        try:
            fut.set_result(row)
        except Exception:
            continue  # caller cancelled this request; the rest of
            # the window must still get their results
        metrics.record_request(done - enqueued[i])


class _Window:
    """One coalesced window riding the stage queues."""

    __slots__ = (
        "examples", "futures", "enqueued", "engine", "owned",
        "parent_span_id", "tree", "rows", "bucket", "host_tree",
        "pool_key", "pool_gen", "device_tree", "out", "valid",
        "fallback", "t_compute0",
    )

    def __init__(self, examples, futures, enqueued, engine, parent_span_id):
        self.examples = examples
        self.futures = futures
        self.enqueued = enqueued
        self.engine = engine
        self.owned = True
        self.parent_span_id = parent_span_id
        self.tree = None          # assembled batched tree (post-prep)
        self.rows = len(examples)
        self.bucket: Optional[int] = None
        self.host_tree = None     # padded host staging (pooled)
        self.pool_key = None
        self.pool_gen = 0
        self.device_tree = None   # staged on device, pre-compute
        self.out = None           # full padded output (async)
        self.valid = None         # sliced valid rows
        self.fallback = False     # rows > engine.max_bucket: serial
        # chunked apply inside the compute stage
        self.t_compute0 = 0.0


def _leading_np(tree) -> bool:
    """True when every leaf is a host (numpy) array — the poolable,
    host-paddable case. Device-array windows pad/place on device via
    the engine's serial ``_stage`` instead."""
    return all(
        not isinstance(a, jax.Array)
        for a in jax.tree_util.tree_leaves(tree)
    )


class LanePipeline:
    """The stage threads + handoff queues behind one pipelined
    ``MicroBatcher``. Construct via ``MicroBatcher(pipeline_depth=N)``;
    windows enter through ``submit_window`` on the batcher's coalesce
    thread and leave by resolving their request futures in deliver."""

    # stage order drives thread wiring and queue-depth attribution
    STAGES = ("host_prep", "upload", "compute", "deliver")

    def __init__(
        self,
        assemble: Callable[[List[Any]], Tuple[Any, bool]],
        depth: int = DEFAULT_DEPTH,
        name: str = "lane",
        current_metrics: Optional[Callable[[], Any]] = None,
    ):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = depth
        self.name = name
        self._assemble = assemble
        # the staging pool belongs to the LANE, so its byte gauge
        # tracks the engine currently serving it — a window that
        # outlives a swap must not stamp the new pool's footprint onto
        # its retired coalesce-time engine (double-counted series)
        self._current_metrics = current_metrics
        self.pool = HostBufferPool(max_per_key=depth + 1)
        self._queues: Dict[str, "queue.Queue"] = {
            s: queue.Queue(maxsize=depth) for s in self.STAGES
        }
        self._closed = False
        self._threads = [
            threading.Thread(
                target=self._stage_loop,
                args=(stage,),
                name=f"keystone-{name}-{stage}",
                daemon=True,
            )
            for stage in self.STAGES
        ]
        for t in self._threads:
            t.start()

    def _publish_staging_bytes(self, fallback_engine) -> None:
        resolve = self._current_metrics
        self.pool.publish_staging_bytes(
            resolve if resolve is not None
            else lambda: fallback_engine.metrics
        )

    # -- intake (the batcher's coalesce thread) ----------------------------

    def submit_window(
        self,
        examples: List[Any],
        futures: List,
        enqueued: List[float],
        engine,
        parent_span_id: Optional[int],
    ) -> None:
        """Hand one coalesced window to the stage chain. BLOCKS while
        the host-prep queue is full — that block is the backpressure
        signal: pending requests pile up behind the batcher, lane load
        rises, and admission sheds before anything here is unbounded."""
        w = _Window(examples, futures, enqueued, engine, parent_span_id)
        self._queues["host_prep"].put(w)
        engine.metrics.set_stage_queue_depth(
            "host_prep", self._queues["host_prep"].qsize()
        )

    # -- stage threads -----------------------------------------------------

    def _stage_loop(self, stage: str) -> None:
        inbox = self._queues[stage]
        i = self.STAGES.index(stage)
        outbox = (
            self._queues[self.STAGES[i + 1]]
            if i + 1 < len(self.STAGES) else None
        )
        fn = getattr(self, f"_{stage}")
        while True:
            w = inbox.get()
            if w is _SENTINEL:
                if outbox is not None:
                    outbox.put(_SENTINEL)
                return
            t0 = time.perf_counter()
            try:
                with get_tracer().span(
                    f"pipeline.{stage}",
                    parent_id=w.parent_span_id,
                    engine=w.engine.name,
                    window=len(w.futures),
                    bucket=w.bucket or 0,
                ):
                    fn(w)
                w.engine.metrics.record_stage(
                    stage, time.perf_counter() - t0
                )
            except Exception as e:
                self._fail_window(w, e)
                continue
            w.engine.metrics.set_stage_queue_depth(stage, inbox.qsize())
            if outbox is not None:
                outbox.put(w)

    def _fail_window(self, w: _Window, err: Exception) -> None:
        """Resolve every future with the stage error (never hang
        callers) and recycle any pooled buffer the window held."""
        if w.pool_key is not None:
            self.pool.release(w.pool_key, w.pool_gen, w.host_tree)
            w.pool_key = None
        for fut in w.futures:
            if not fut.done():
                try:
                    fut.set_exception(err)
                except Exception:
                    pass  # caller cancelled concurrently

    # stage 2: assemble (stack / host featurize) + pad on host into a
    # pooled staging buffer
    def _host_prep(self, w: _Window) -> None:
        engine = w.engine
        # chaos point: stall the prep stage (a slow tokenizer RPC /
        # feature-store brownout). The sleep holds THIS stage thread,
        # so the bounded handoff queues fill, submit_window blocks,
        # lane load rises, and admission sheds — the end-to-end
        # backpressure chain is exactly what the experiment verifies.
        if faults.armed():
            spec = faults.fire(
                "pipeline.host_prep.stall", {"engine": engine.name}
            )
            if spec is not None and spec.delay_ms > 0:
                time.sleep(spec.delay_ms / 1e3)
        w.tree, w.owned = self._assemble(w.examples)
        w.examples = None  # window owns the batched tree from here
        leaves, treedef = jax.tree_util.tree_flatten(w.tree)
        w.rows = leaves[0].shape[0]
        if w.rows > engine.max_bucket:
            # a pinned max_batch wider than a post-swap engine's largest
            # bucket: fall back to the engine's chunked serial apply in
            # the compute stage (degraded, never wrong)
            w.fallback = True
            return
        w.bucket = engine.bucket_for(w.rows)
        if not _leading_np(w.tree):
            # device-array window: pad/place on device exactly like the
            # serial path; upload becomes a pass-through
            w.device_tree = engine._stage(
                w.tree, w.rows, w.bucket, owned=w.owned
            )
            w.tree = None
            return
        key = (
            w.bucket, treedef,
            tuple((a.shape[1:], a.dtype.str) for a in leaves),
        )
        bucket = w.bucket

        def alloc():
            return treedef.unflatten([
                np.zeros((bucket,) + a.shape[1:], a.dtype)
                for a in leaves
            ])

        w.pool_gen, buffers = self.pool.acquire(key, alloc)
        w.pool_key = key
        self._publish_staging_bytes(engine)
        # attach the buffers to the window BEFORE the fill: if a
        # misbehaving featurize hook makes host_stage raise (e.g. a
        # leaf with a mismatched leading dim), _fail_window must
        # recycle the real buffers — releasing a half-built window's
        # host_tree=None would poison the pool key for every later
        # window sharing it
        w.host_tree = buffers
        engine.host_stage(w.tree, w.rows, bucket, out=buffers)
        w.tree = None

    # stage 3: H2D transfer. The pooled host buffer is NOT released
    # here: backends may stage host arrays zero-copy (the CPU backend
    # does — a device_put'd array can read the numpy buffer as late as
    # the consuming execution), so "transfer ready" does not mean
    # "host buffer consumed". The buffer rides with the window and
    # frees once its COMPUTE output is ready — the first point the
    # inputs are provably consumed. depth+1 pooled buffers per key
    # keep prep/upload/compute fully overlapped despite the longer
    # hold.
    def _upload(self, w: _Window) -> None:
        if w.fallback or w.device_tree is not None:
            return
        staged = w.engine.upload_staged(w.host_tree)
        jax.block_until_ready(staged)
        w.device_tree = staged

    # stage 4: the compiled bucket program with donated inputs; the
    # ready sync here is the completion-timed dispatch number the
    # serial path records at apply(sync=True)
    def _compute(self, w: _Window) -> None:
        engine = w.engine
        w.t_compute0 = time.perf_counter()
        if w.fallback:
            # oversized window (pinned max_batch > a post-swap engine's
            # largest bucket): the engine's chunked serial apply
            w.valid = engine.apply(w.tree, sync=True, owned=w.owned)
            w.tree = None
            return
        w.out = engine.compute_staged(w.device_tree, w.rows, w.bucket)
        w.device_tree = None  # donated — never touch it again
        jax.block_until_ready(w.out)
        engine.metrics.record_dispatch_complete(
            time.perf_counter() - w.t_compute0
        )
        if w.pool_key is not None:
            # output ready == inputs consumed: the pooled host buffer
            # is finally safe to hand to a later window's prep
            self.pool.release(w.pool_key, w.pool_gen, w.host_tree)
            w.pool_key = None
            w.host_tree = None
            self._publish_staging_bytes(engine)

    # stage 5: slice valid rows, resolve futures, close the loop on
    # request latency + window-rate series (the single-host-gather
    # rationale lives on resolve_window_futures)
    def _deliver(self, w: _Window) -> None:
        metrics = w.engine.metrics
        if w.valid is None:
            w.valid = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[: w.rows], w.out
            )
            w.out = None
        resolve_window_futures(metrics, w.valid, w.futures, w.enqueued)
        metrics.record_window()

    # -- lifecycle ---------------------------------------------------------

    def on_swap(self) -> None:
        """Engine swapped behind the batcher: rebuild the staging pool
        (bucket sizes may have changed). Windows already in the stages
        carry their coalesce-time engine and finish on it; their
        buffers drop instead of re-pooling (generation bump)."""
        self.pool.reset()

    def close(self, timeout: Optional[float] = 10.0) -> None:
        """Flush in-flight windows through every stage and stop the
        threads. Caller (``MicroBatcher.close``) has already drained
        its pending queue into ``submit_window``."""
        if self._closed:
            return
        self._closed = True
        self._queues["host_prep"].put(_SENTINEL)
        deadline = (
            time.perf_counter() + timeout if timeout is not None else None
        )
        for t in self._threads:
            remaining = (
                None if deadline is None
                else max(0.1, deadline - time.perf_counter())
            )
            t.join(remaining)
        if any(t.is_alive() for t in self._threads):
            logger.warning(
                "lane pipeline %s still draining after %.1fs close "
                "timeout (cold compile in flight?); in-flight futures "
                "resolve as it finishes", self.name, timeout,
            )


__all__ = [
    "DEFAULT_DEPTH",
    "HostBufferPool",
    "HostFeaturize",
    "LanePipeline",
]
