"""Bucketed compiled execution for ``FittedPipeline``.

``FittedPipeline.jit_batch`` stages the whole batched apply path into
one XLA program — but one program PER BATCH SHAPE: every distinct
request size costs a full compile (seconds on a cold shape). A
``CompiledPipeline`` instead fixes a small set of power-of-two row
buckets, zero-pads each incoming batch up to the smallest covering
bucket, and dispatches the bucket's compiled program; steady-state
traffic therefore compiles at most ``len(buckets)`` programs, however
many distinct batch sizes arrive. Zero pad rows are safe by the
``Dataset`` padding discipline (parallel/dataset.py: rows past ``n``
are zeros and transformers must map them to values safe to keep as
padding); outputs are sliced back to the valid rows.

Input buffers the engine stages are donated to XLA on backends that
support donation (TPU/GPU), so serving doesn't hold two copies of each
padded batch. The optional sharded variant places each staged batch
over the mesh data axis for multi-chip serving — same program, one
compile per bucket, XLA inserts the collectives.

**Mesh-sharded parameters** (``param_sharding=``): the model axis.
``shard=`` scales the *batch*; a model whose parameters exceed one
chip's HBM needs the *weights* split. ``param_sharding`` resolves a
declarative rule set (``serving/sharding.py``: regex over the fitted
pipeline's named param pytree -> ``PartitionSpec``; ``True`` = the
default solver-output rules) against the pipeline, places each param
over the mesh's model axis via ``NamedSharding`` once at construction,
and traces every bucket program with the params as explicit *arguments*
(``ParamBinder``) instead of baked-in constants — each device's
executable holds only its weight shards. Composes with ``shard=``
(rows over ``data``, weights over ``model``, one 2-D mesh) and with
``featurize=`` (the fused stage's params stay baked/replicated; pass
rules matching them to split those too — they ride the same binder
only for the model pipeline).

**Device-side featurization** (``featurize=``): a second fitted
pipeline — a pure-JAX featurize chain such as the ``ops/images``
Convolver/LCS/FisherVector stacks — fused IN FRONT of the model into
the same per-bucket program. Requests then stage **raw bytes** (e.g.
``uint8`` images: 4× fewer H2D bytes than the f32 features), and the
cast + featurize + predict all ride the single compiled dispatch; XLA
fuses across the featurize/model boundary and the bucket cost model
(MFU/roofline/goodput) automatically accounts for the fused FLOPs.
This is the device-side counterpart of the batcher's ``host_featurize``
seam — use that one for featurizers that can't trace (native/items
code); use this one to kill the host-prep + upload bottleneck for
chains that are already jax. Buckets stay row counts; the raw
per-example shape rides the example spec exactly like any array input,
and the ``keystone_serving_h2d_bytes_total`` counter makes the
wire-bytes reduction a scraped fact.

The dispatch path is factored into stage primitives so the staged lane
pipeline (``serving/pipeline.py``) can run them on separate threads —
``host_stage`` (pad on host into a pooled reusable buffer),
``upload_staged`` (H2D placement, sharded when the engine is), and
``compute_staged`` (the compiled bucket program + dispatch counters) —
while the serial ``apply``/``_dispatch`` path composes exactly the same
primitives inline, which is what makes pipelined results bit-identical
to serial ones. Owned-buffer contract: a staged tree handed to
``compute_staged`` is engine-private by construction (``host_stage``
wrote it, or the caller promised ``owned=True``) and is donated to XLA
where the backend supports it — callers must never reuse buffers they
passed with that promise.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.loadgen import faults
from keystone_tpu.observability import device as device_obs
from keystone_tpu.observability.tracing import get_tracer
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel.dataset import Dataset, _leading_dim
from keystone_tpu.serving.metrics import ServingMetrics

logger = logging.getLogger(__name__)

DEFAULT_BUCKETS = (8, 64, 512)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


class _ParamBoundFn:
    """Adapts a two-argument ``(params, batch)`` program — what a
    model-sharded engine traces — to the engine's one-argument fn
    convention, binding the placed (sharded, committed) param tree.
    Every dispatch passes the same placed arrays; only the batch
    varies. ``lower`` delegates for the cost-model path."""

    def __init__(self, fn, params):
        self.fn = fn
        self.params = params

    def __call__(self, staged):
        return self.fn(self.params, staged)

    def lower(self, staged):
        return self.fn.lower(self.params, staged)


class CompiledPipeline:
    """A ``FittedPipeline`` behind a fixed set of compiled batch shapes.

    Parameters
    ----------
    pipeline:  the fitted (transformer-only) pipeline; its whole batched
               apply path must be traceable (array-mode nodes only —
               host-side items-mode nodes can't stage; use
               ``FittedPipeline.apply`` for those).
    buckets:   ascending row buckets; a batch of n rows dispatches the
               smallest bucket >= n, and batches larger than the biggest
               bucket are chunked through it.
    donate:    donate staged input buffers to XLA (auto-disabled on
               backends without donation support, e.g. CPU).
    shard:     place each staged batch over the mesh data axis
               (multi-chip serving). Buckets are rounded up to a
               multiple of the mesh's data-shard count so every shard
               gets equal rows.
    featurize: optional fitted featurize pipeline fused IN FRONT of
               ``pipeline`` inside every bucket program (device-side
               featurization): callers stage RAW examples (e.g. uint8
               images) and one compiled dispatch runs
               ``pipeline(featurize(raw))``. Must be traceable
               (array-mode, pure JAX) like ``pipeline`` itself.
    param_sharding: shard the MODEL over the mesh's model axis
               (serving/sharding.py): ``True`` resolves the default
               rule set against the pipeline's named params, a
               sequence of ``(regex, PartitionSpec)`` rules or a
               ``{name: spec}`` dict partitions explicitly. Params
               are placed once (sharded ``NamedSharding``) and become
               arguments of every bucket program, so each device
               holds only its shard — models bigger than one chip's
               HBM serve on the mesh. Buckets round up to the mesh's
               data-shard count exactly as under ``shard=`` (staged
               batches are mesh-placed either way).
               ``param_sharding_unmatched="replicate"`` downgrades
               unmatched-param errors to replication.
    """

    def __init__(
        self,
        pipeline,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        *,
        donate: bool = True,
        shard: bool = False,
        mesh=None,
        metrics: Optional[ServingMetrics] = None,
        name: Optional[str] = None,
        featurize: Any = None,
        param_sharding: Any = None,
        param_sharding_unmatched: str = "error",
    ):
        if not buckets:
            raise ValueError("need at least one bucket")
        if any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive: {buckets}")
        self.pipeline = pipeline
        self.featurize = featurize
        self.shard = shard
        self.mesh = mesh
        if shard or param_sharding:
            self.mesh = mesh or mesh_lib.current_mesh()
        # -- model axis: declarative param sharding over the mesh ------
        self._binder = None
        self.param_sharding: Optional[Dict[str, Any]] = None
        self._placed_params = None
        if param_sharding:
            from keystone_tpu.serving import sharding as sharding_lib

            self._binder = sharding_lib.ParamBinder(pipeline)
            self.param_sharding = sharding_lib.resolve_param_sharding(
                param_sharding, pipeline,
                params=self._binder.params,
                unmatched=param_sharding_unmatched,
            )
            shard_fns = sharding_lib.make_shard_fns(
                self.param_sharding, self.mesh
            )
            # placed ONCE: sharded committed arrays, reused as the
            # param argument of every bucket program's every dispatch
            self._placed_params = {
                name: fn(self._binder.params[name])
                for name, fn in shard_fns.items()
            }
        self.model_sharded = self._binder is not None
        # staged batches are mesh-placed whenever the engine is mesh-
        # anything: data-sharded batches for shard=, and mesh-committed
        # (data axis may be size 1) batches for model-sharded programs
        # so jit sees committed input shardings consistent with the
        # placed params
        self._place_batch = self.shard or self.model_sharded
        if self._place_batch:
            # every mesh-placed batch splits its leading axis over the
            # data axis — buckets must divide evenly whether the engine
            # shards rows, weights, or both (a model-sharded engine on
            # a mesh with a >1 data axis would otherwise fail every
            # device_put for the undivisible buckets)
            nshards = mesh_lib.n_data_shards(self.mesh)
            buckets = [_round_up(b, nshards) for b in buckets]
        self.buckets: Tuple[int, ...] = tuple(sorted(set(int(b) for b in buckets)))
        self.metrics = metrics if metrics is not None else ServingMetrics()
        # every engine is scrapeable: its per-bucket compile/dispatch
        # counters and latency quantiles export through the global
        # MetricsRegistry (weakref bridge — registration never extends
        # this engine's lifetime) under the `engine` label
        self.name = self.metrics.register(engine=name)
        # device truth for the MFU/roofline series: detected peaks of
        # the local device kind (None on unknown hardware -> those
        # series stay absent) scaled by the engine's device count
        devices = device_obs.device_table()
        peak_flops, peak_membw = (
            (devices[0]["peak_flops"],
             devices[0]["peak_membw_bytes_per_s"])
            if devices else (None, None)
        )
        n_devices = 1
        if (self.shard or self.model_sharded) and self.mesh is not None:
            # the engine's device set is the MESH, counted exactly once
            # whether rows, weights, or both are sharded over it — the
            # MFU denominator (peak x n_devices) must match what the
            # program actually runs on, and N lanes sharing one mesh
            # each count the mesh, never lanes x mesh
            n_devices = int(getattr(self.mesh.devices, "size", 1))
        self.metrics.set_device_peaks(
            peak_flops, peak_membw, n_devices=n_devices
        )
        self.donate = donate and jax.default_backend() in ("tpu", "gpu")
        self._fns: Dict[int, Callable] = {}
        # a MicroBatcher worker and direct apply() callers may race to
        # create a bucket's jit fn; two fns would mean two traces, and
        # the <= len(buckets) compile bound is the subsystem's contract
        self._fn_lock = threading.Lock()

    # -- compiled-program management ---------------------------------------

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket covering ``n`` rows (callers chunk above the
        largest bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} rows exceeds the largest bucket "
            f"{self.max_bucket}; chunk it (engine.apply does)"
        )

    def _make_jit(self, bucket: int) -> Callable:
        """A fresh polymorphic jit fn for ``bucket``. With a fused
        featurize stage, the whole featurize∘model composition traces
        into ONE program — XLA fuses across the boundary and the cast
        from the raw wire dtype happens on device, inside it."""
        run = self.pipeline._batch_run
        feat_run = (
            self.featurize._batch_run
            if self.featurize is not None else None
        )
        metrics = self.metrics
        binder = self._binder

        if binder is not None:
            # model-sharded: params are explicit program ARGUMENTS —
            # jit reads their committed NamedShardings (and the staged
            # batch's mesh placement) and GSPMD partitions the program;
            # each device's executable holds only its weight shards
            def staged_sharded(params, arr):
                metrics.record_trace(bucket)
                if feat_run is not None:
                    arr = feat_run(arr)
                return binder.run(params, arr)

            return _ParamBoundFn(
                jax.jit(
                    staged_sharded,
                    donate_argnums=(1,) if self.donate else (),
                ),
                self._placed_params,
            )

        def staged(arr):
            # executes at TRACE time only — one increment per XLA
            # compile of this bucket, zero on compiled dispatches
            metrics.record_trace(bucket)
            if feat_run is not None:
                arr = feat_run(arr)
            return run(arr)

        return jax.jit(
            staged, donate_argnums=(0,) if self.donate else ()
        )

    def _fn(self, bucket: int) -> Callable:
        fn = self._fns.get(bucket)
        if fn is not None:
            return fn
        with self._fn_lock:
            fn = self._fns.get(bucket)
            if fn is not None:
                return fn
            fn = self._make_jit(bucket)
            self._fns[bucket] = fn
            return fn

    # -- staging -----------------------------------------------------------

    def _stage(
        self, tree: Any, rows: int, bucket: int, owned: bool = False
    ) -> Any:
        """Pad a pytree of row-major arrays up to ``bucket`` rows with
        zeros (valid by the Dataset zero-pad discipline) and place it.
        ``owned=True`` promises the buffers are engine/batcher-private
        (safe to donate without the protective copy)."""
        pad = bucket - rows

        def pad_leaf(a):
            # caller-owned only if it arrived as a device array; numpy
            # input becomes an engine-private buffer on the H2D transfer
            caller_owned = isinstance(a, jax.Array) and not owned
            a = jnp.asarray(a)
            if pad:
                return jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
                )
            if self.donate and caller_owned:
                # exact-size caller-owned buffer: copy so donation can't
                # invalidate an array the caller still holds
                return jnp.array(a, copy=True)
            return a

        staged = jax.tree_util.tree_map(pad_leaf, tree)
        if self._place_batch:
            staged = jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    a, mesh_lib.data_sharding(self.mesh, ndim=a.ndim)
                ),
                staged,
            )
        return staged

    # -- pipeline stage primitives (serving/pipeline.py runs these on
    # -- separate threads; _dispatch composes them inline) ------------------

    def host_stage(self, tree: Any, rows: int, bucket: int, out: Any) -> Any:
        """HOST-side pad of a numpy pytree up to ``bucket`` rows with
        zeros — the pipelined host-prep stage. ``out`` is a matching
        pytree of preallocated ``(bucket, ...)`` buffers (the reusable
        staging pool): valid rows are copied in and the pad region
        zeroed, so steady-state windows allocate nothing on the host.
        Returns ``out``."""
        def fill_leaf(buf, a):
            np.copyto(buf[:rows], np.asarray(a))
            if bucket > rows:
                buf[rows:] = 0
            return buf

        return jax.tree_util.tree_map(fill_leaf, out, tree)

    def upload_staged(self, staged_host: Any) -> Any:
        """H2D placement of a host-staged (already padded) tree — the
        pipelined upload stage. Sharded engines place over the mesh
        data axis; the transfer is async (callers that need the host
        buffers back block on the returned arrays). The device buffers
        are engine-private (the transfer copies), so downstream compute
        may donate them."""
        if self._place_batch:
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    a, mesh_lib.data_sharding(self.mesh, ndim=a.ndim)
                ),
                staged_host,
            )
        return jax.tree_util.tree_map(jax.device_put, staged_host)

    def compute_staged(self, staged: Any, rows: int, bucket: int) -> Any:
        """Dispatch the bucket's compiled program over an
        already-staged (padded + placed) tree and record the dispatch
        counters. ``staged`` must be engine-private — it is donated to
        XLA where the backend supports donation. Returns the full
        padded output (async; callers slice to ``rows`` valid rows and
        own the sync point)."""
        # chaos point: fail the whole window at dispatch (match:
        # engine=<name> to target one lane's engine). Serial apply and
        # the pipelined compute stage both pass through here, so the
        # experiment exercises whichever path traffic does. Unarmed:
        # the armed() gate keeps this a no-op (no ctx dict built).
        if faults.armed() and faults.fire(
            "engine.dispatch.error", {"engine": self.name}
        ) is not None:
            raise faults.FaultInjected(
                "engine.dispatch.error", engine=self.name, bucket=bucket
            )
        # the wire-bytes fact: what this dispatch actually shipped to
        # the device (padded rows included — padding is real traffic on
        # the H2D path). nbytes is array METADATA (shape × itemsize),
        # not a device read, so this stays sync-free; device-featurize
        # engines stage raw uint8 here and the counter is how the ~4×
        # reduction over f32 features becomes a scraped fact.
        h2d_bytes = sum(
            int(getattr(a, "nbytes", 0))
            for a in jax.tree_util.tree_leaves(staged)
        )
        out = self._fn(bucket)(staged)
        self.metrics.record_dispatch(bucket, rows, h2d_bytes=h2d_bytes)
        return out

    # -- serving entry points ----------------------------------------------

    def apply(
        self, data: Any, sync: bool = False, owned: bool = False
    ) -> Any:
        """Serve one batch: pad to the covering bucket (chunking through
        the largest bucket when oversized), dispatch the compiled
        program(s), and return outputs sliced to the valid rows.

        ``data`` is a Dataset, an array, or a pytree of arrays sharing a
        leading example axis. ``sync=True`` blocks until the whole
        result is ready (one host sync, after every chunk is
        enqueued). ``owned=True`` asserts the input buffers belong to
        the engine's caller-of-record (MicroBatcher, warmup) and may be
        donated without the exact-bucket-size protective copy — don't
        pass it for arrays you still need."""
        if isinstance(data, Dataset):
            rows = data.n
            tree = data.array()
        else:
            tree = data
            rows = _leading_dim(tree)
        if rows == 0:
            raise ValueError("cannot serve an empty batch")
        outs: List[Any] = []
        # when chunking happened every slice is a strict subrange —
        # always a fresh engine-private buffer, safe to donate without
        # the protective copy; only the single-chunk identity slice can
        # alias the caller's array
        chunk_owned = owned or rows > self.max_bucket
        t0 = time.perf_counter()
        start = 0
        while start < rows:
            take = min(self.max_bucket, rows - start)
            chunk = jax.tree_util.tree_map(
                lambda a: a[start : start + take], tree
            )
            # every chunk enqueues async — staging chunk k+1 overlaps
            # execution of chunk k; the one host sync comes at the end
            outs.append(self._dispatch(chunk, take, owned=chunk_owned))
            start += take
        result = outs[0] if len(outs) == 1 else jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *outs
        )
        if sync:
            jax.block_until_ready(result)
            # the completion-timed dispatch number: this sync is the
            # first point the device work is provably done (the
            # per-chunk timer above stops at enqueue — execution is
            # async past the compiled call, so that number alone
            # under-reported device time; it survives as the separate
            # dispatch_enqueue series). Async callers own their sync
            # point and record nothing here; the pipelined compute
            # stage records its own completion number per window.
            self.metrics.record_dispatch_complete(
                time.perf_counter() - t0
            )
        return result

    def _dispatch(self, chunk: Any, rows: int, owned: bool = False) -> Any:
        bucket = self.bucket_for(rows)
        with get_tracer().span(
            "serving.dispatch", engine=self.name, bucket=bucket, rows=rows
        ):
            t0 = time.perf_counter()
            staged = self._stage(chunk, rows, bucket, owned=owned)
            out = self.compute_staged(staged, rows, bucket)
            valid = jax.tree_util.tree_map(lambda a: a[:rows], out)
            self.metrics.record_dispatch_enqueue(
                time.perf_counter() - t0
            )
        return valid

    def warmup(
        self,
        example: Any = None,
        batch: Any = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> Dict[int, float]:
        """Compile every bucket up front (zero cold compiles at traffic
        time; with the persistent compilation cache wired — see
        ``parallel.runtime.setup_compilation_cache`` — a restarted
        server replays these compiles from disk).

        The per-example shape/dtype spec comes from ``example`` (a
        pytree for ONE example, no leading axis) or ``batch`` (a pytree
        WITH a leading axis, e.g. any representative request). Returns
        bucket -> compile wall seconds."""
        if (example is None) == (batch is None):
            raise ValueError("pass exactly one of example= or batch=")
        if batch is not None and isinstance(batch, Dataset):
            batch = batch.array()
        leaves, treedef = jax.tree_util.tree_flatten(
            batch if batch is not None else example
        )
        drop = 1 if batch is not None else 0
        specs = [
            (jnp.asarray(a).shape[drop:], jnp.asarray(a).dtype)
            for a in leaves
        ]
        want = list(buckets) if buckets is not None else list(self.buckets)
        unknown = [b for b in want if b not in self.buckets]
        if unknown:  # validate BEFORE compiling anything: a bad bucket
            # late in the list must not leave a half-warmed engine
            raise ValueError(
                f"unknown bucket(s) {unknown} (have {self.buckets})"
            )
        times: Dict[int, float] = {}
        for b in want:
            zeros = treedef.unflatten(
                [jnp.zeros((b,) + s, d) for s, d in specs]
            )
            fn = self._fn(b)
            staged = self._stage(zeros, b, b, owned=True)
            # outside the timed window: the returned numbers are the
            # dispatch's compile wall, not cost-model extraction
            self._register_cost_model(b, fn, staged)
            t0 = time.perf_counter()
            out = fn(staged)
            jax.block_until_ready(out)
            times[b] = time.perf_counter() - t0
        return times

    def _register_cost_model(self, bucket: int, fn, staged) -> None:
        """Pull the bucket program's static XLA cost model — FLOPs,
        bytes accessed — and register it on the metrics (the
        MFU/roofline/goodput input).

        Reads ``fn.lower(staged).cost_analysis()``: lowering shares the
        jit TRACE cache (the compile-count contract holds, and the
        ``fn(staged)`` dispatch that follows retraces nothing) and the
        analysis runs on the lowered module — no XLA compile. The
        ``lower().compile()`` executable is NOT shared with the jit
        dispatch path (measured: compiling here would compile every
        bucket twice), so the executable — which also carries
        ``memory_analysis()``'s temp-HBM number — is built only when
        the persistent compilation cache is configured (the dispatch's
        own compile then replays from disk instead of paying the
        program twice). Best-effort by design: backends whose lowering
        or analyses fail (or report nothing) leave the model ABSENT —
        serving and the scrape surface must work identically without
        it."""
        try:
            lowered = fn.lower(staged)
            compiled = None
            if getattr(jax.config, "jax_compilation_cache_dir", None):
                compiled = lowered.compile()
            model = device_obs.compiled_cost_model(lowered)
            if compiled is not None:
                model.update(device_obs.compiled_cost_model(compiled))
            self.metrics.set_cost_model(bucket, model)
        except Exception:
            logger.debug(
                "no AOT cost analysis for bucket %d", bucket, exc_info=True
            )

    __call__ = apply
