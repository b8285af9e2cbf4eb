"""``Dataset`` — the framework's N-example collection type (the RDD stand-in).

Four physical modes:

- **array mode**: a pytree of arrays (usually one matrix) with a leading
  example axis, optionally zero-padded to a multiple of the mesh's data-shard
  count and placed with a ``NamedSharding`` on the data axis. This is the fast
  path: transformers become batched jnp ops, solvers see one sharded matrix,
  XLA inserts the collectives.
- **items mode**: a host-side list of per-example Python objects (ragged
  arrays, images of varying size, token lists). This replaces RDDs of
  non-uniform records; operators map over it on host and convert to array
  mode as soon as shapes become uniform. The data decide, not the caller:
  ``uniform_array`` hands a node the items as one array when they all
  share one shape and dtype, and ``Transformer._bucketed_batch`` then
  returns array mode; items of two or more shapes become shape groups.
- **shape groups**: ragged items as one array a shape (``grouped``), each
  with the places of its rows in the data set. Per-item nodes are not run
  on it at once: ``then`` notes them, and whoever asks for the data
  (``Cacher``, an estimator, ``padded`` / ``items``) has the noted run go
  through a chunk of rows at a time, a chunk's size from bytes
  (``parallel/chunks.py``), one program per (run, shape, chunk). Only
  what is asked for is ever whole: a node read by two others is computed
  once for each. Groups whose rows end up of one shape join into array
  mode in the data set's order.
- **host-blocks mode**: a feature matrix column-blocked into HOST-RAM
  numpy arrays (each (padded_n, w_i), C-contiguous). This is the
  out-of-aggregate-HBM training substrate: the reference caches features
  in cluster RAM and streams them block-by-block through the block
  solvers (BlockLinearMapper.scala:50-73 iterates per-block feature
  RDDs; AutoCacheRule.scala:559-602 budgets 75% of cluster memory for
  the cache). Here host RAM is the cache tier and the BCD solvers
  double-buffer each slab onto the chip per pass — a fit's feature
  footprint is bounded by host RAM, not HBM. Blocks mirror the
  reference's Seq[RDD] layout, so slabs transfer without a strided-copy
  repack.

Padding discipline: ``n`` is the valid example count; rows past ``n`` are
zeros. Reductions that care divide by ``n`` or use ``mask()``; zero rows
contribute nothing to Gram matrices / sums, so linear solvers are exact
without explicit masking.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.observability.registry import get_global_registry
from keystone_tpu.observability.tracing import span
from keystone_tpu.parallel import chunks as chunks_lib
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel.chunks import leading_dim as _leading_dim


def count_chunked(
    items: int, chunks: int, padded: int, array_items: int, groups: int = 0
) -> None:
    """Publish one pass of items through chunk programs
    (``Transformer._chunked_batch``, a shape-grouped ``Dataset``):
    ``array_items`` of its ``items`` left as arrays, the others were cut
    back into items, a slice each."""
    reg = get_global_registry()
    reg.counter(
        "keystone_workflow_items_total",
        "items through Transformer._bucketed_batch / _chunked_batch",
    ).inc(by=items)
    reg.counter(
        "keystone_workflow_array_items_total",
        "items of those that left in array mode, never cut into items",
    ).inc(by=array_items)
    reg.counter(
        "keystone_workflow_chunks_total",
        "jit(vmap) chunk dispatches of Transformer._bucketed_batch",
    ).inc(by=chunks)
    reg.counter(
        "keystone_workflow_padded_rows_total",
        "rows that filled a short chunk up to the chunk's shape: zeros, "
        "or in a shape group rows computed a second time",
    ).inc(by=padded)
    reg.counter(
        "keystone_workflow_item_slices_total",
        "per-item slices that cut chunk outputs back into items",
    ).inc(by=items - array_items)
    reg.counter(
        "keystone_workflow_shape_groups_total",
        "shape groups that went through chunk programs as arrays",
    ).inc(by=groups)


class HostPuts:
    """The host-to-device puts one workflow call issues for its items."""

    def __init__(self) -> None:
        self.puts = 0
        self.nbytes = 0

    def asarray(self, x: Any) -> Any:
        """``jnp.asarray`` of one leaf of an item; a leaf that arrives on
        the host is one put."""
        if isinstance(x, jax.Array):
            return x
        a = jnp.asarray(x)
        self.puts += 1
        self.nbytes += a.nbytes
        return a

    def count(self, host_items: int) -> None:
        """Publish: ``host_items`` items arrived on the host. Items
        already on the device count nowhere, so transfers over items is
        the puts one host item costs."""
        if not host_items:
            return
        reg = get_global_registry()
        reg.counter(
            "keystone_workflow_h2d_items_total",
            "items that reached the workflow layer as host arrays",
        ).inc(by=host_items)
        reg.counter(
            "keystone_workflow_h2d_transfers_total",
            "host-to-device puts the workflow layer issued for host items",
        ).inc(by=self.puts)
        reg.counter(
            "keystone_workflow_h2d_bytes_total",
            "bytes of the workflow layer's host-to-device puts",
        ).inc(by=self.nbytes)


class Dataset:
    def __init__(
        self,
        *,
        arrays: Any = None,
        items: Optional[List[Any]] = None,
        host_blocks: Optional[List[np.ndarray]] = None,
        groups: Optional[List[tuple]] = None,
        steps: tuple = (),
        n: Optional[int] = None,
    ):
        modes = sum(
            x is not None for x in (arrays, items, host_blocks, groups)
        )
        if modes != 1:
            raise ValueError(
                "exactly one of arrays/items/host_blocks/groups required"
            )
        self._arrays = arrays
        self._items = items
        self._host_blocks = host_blocks
        # shape groups: [(places of the rows in the data set, one array)]
        # and the per-row functions noted for them, not yet run
        self._groups = groups
        self._steps = tuple(steps)
        if groups is not None:
            self._n = int(n) if n is not None else sum(
                len(p) for p, _ in groups
            )
        elif arrays is not None:
            self._n = int(n) if n is not None else _leading_dim(arrays)
        elif host_blocks is not None:
            if not host_blocks:
                raise ValueError("host_blocks must be non-empty")
            rows = {b.shape[0] for b in host_blocks}
            if len(rows) != 1:
                raise ValueError(
                    f"host blocks disagree on row count: {sorted(rows)}"
                )
            self._n = int(n) if n is not None else host_blocks[0].shape[0]
        else:
            self._n = len(items)
        self._cached = False
        self._uploaded: Any = None
        self._grouped: Optional["Dataset"] = None

    # -- constructors ------------------------------------------------------

    @staticmethod
    def of(data: Any) -> "Dataset":
        """Lift a list/array into a Dataset (lists -> items mode unless all
        leaves are uniform arrays, arrays -> array mode)."""
        if isinstance(data, Dataset):
            return data
        if isinstance(data, (list, tuple)):
            return Dataset(items=list(data))
        return Dataset(arrays=jnp.asarray(data))

    @staticmethod
    def from_array(arrays: Any, n: Optional[int] = None) -> "Dataset":
        return Dataset(arrays=arrays, n=n)

    @staticmethod
    def from_items(items: Sequence[Any]) -> "Dataset":
        return Dataset(items=list(items))

    @staticmethod
    def from_groups(
        groups: Sequence[tuple], n: Optional[int] = None, steps: tuple = ()
    ) -> "Dataset":
        """Ragged items as one array a shape: ``groups`` is [(places,
        array)], row j of ``array`` being item ``places[j]``."""
        return Dataset(groups=list(groups), steps=steps, n=n)

    @staticmethod
    def from_host_blocks(
        blocks: Sequence[np.ndarray], n: Optional[int] = None
    ) -> "Dataset":
        """Column-blocked feature matrix resident in host RAM (the
        cluster-RAM feature cache of BlockLinearMapper.scala:50-73).
        Each block is (padded_n, w_i); solvers stream one slab to the
        device at a time, so the fit is bounded by host RAM, not HBM.
        Blocks are made C-contiguous here (one-time cost) so every
        later ``device_put`` is a straight memcpy, never a strided
        repack inside the transfer path."""
        return Dataset(
            host_blocks=[np.ascontiguousarray(b) for b in blocks], n=n
        )

    @staticmethod
    def from_host_array(
        arr: np.ndarray, block_size: int, n: Optional[int] = None
    ) -> "Dataset":
        """Split one host matrix into contiguous column blocks (test /
        convenience path; production featurizers emit blocks directly)."""
        blocks = [
            arr[:, s : s + block_size]
            for s in range(0, arr.shape[1], block_size)
        ]
        return Dataset.from_host_blocks(blocks, n=n)

    @staticmethod
    def host_blocks_from_batches(
        batches, block_size: int, n: Optional[int] = None
    ) -> "Dataset":
        """Accumulate ROW batches of features (a featurize stream's
        output — e.g. ``featurize(chunk)`` per loader batch) into
        host-RAM COLUMN blocks: the glue between the out-of-core input
        pipeline and the out-of-aggregate-HBM solvers, covering the
        reference's featurize→cache-in-cluster-RAM→solve flow
        (ImageNetSiftLcsFV.scala:106-142) without the features ever
        being resident in HBM or as one host matrix.

        ``batches`` yields (rows_i, D) arrays (device or host; device
        batches are pulled to host here — on the producer side keep the
        featurize chunk loop async and let this pull be the sync
        point). Peak host memory is the features plus one column-block
        copy (the per-block row chunks are freed as each block is
        assembled)."""
        per_block: List[List[np.ndarray]] = []
        total = 0
        width: Optional[int] = None
        for batch in batches:
            host = np.asarray(batch)
            total += host.shape[0]
            d = host.shape[1]
            if width is None:
                if d == 0:
                    raise ValueError("zero-width feature batch")
                width = d
                per_block = [
                    [] for _ in range(-(-d // block_size))
                ]
            elif d != width:
                raise ValueError(
                    f"feature width changed mid-stream: {d} vs {width}"
                )
            for bi in range(len(per_block)):
                s = bi * block_size
                # slice views; the final per-block concatenate makes
                # the contiguous copy exactly once
                per_block[bi].append(host[:, s : s + block_size])
        if width is None:
            raise ValueError("empty feature stream")
        blocks = []
        for bi in range(len(per_block)):
            blocks.append(np.concatenate(per_block[bi], axis=0))
            per_block[bi] = []  # free the row chunks as we go
        return Dataset.from_host_blocks(blocks, n=n if n is not None else total)

    # -- inspection --------------------------------------------------------

    @property
    def n(self) -> int:
        return self._n

    def __len__(self) -> int:
        return self._n

    @property
    def is_array(self) -> bool:
        return self._arrays is not None

    @property
    def is_host(self) -> bool:
        return self._host_blocks is not None

    @property
    def is_grouped(self) -> bool:
        return self._groups is not None

    @property
    def host_blocks(self) -> List[np.ndarray]:
        if self._host_blocks is None:
            raise ValueError("not a host-blocks dataset")
        return self._host_blocks

    @property
    def block_widths(self) -> List[int]:
        return [b.shape[1] for b in self.host_blocks]

    @property
    def padded_n(self) -> int:
        if self.is_array:
            return _leading_dim(self._arrays)
        if self.is_host:
            return self._host_blocks[0].shape[0]
        return self._n

    # -- views -------------------------------------------------------------

    def padded(self) -> Any:
        """Arrays with the (possibly padded) leading axis — the solver view."""
        return self.to_array_mode()._arrays

    def array(self) -> Any:
        """Arrays sliced to exactly ``n`` valid rows (unsharded host view)."""
        arrs = self.to_array_mode()._arrays
        if _leading_dim(arrs) == self._n:
            return arrs
        return jax.tree_util.tree_map(lambda a: a[: self._n], arrs)

    def mask(self) -> jnp.ndarray:
        """(padded_n,) float32 validity mask (cached: solvers ask for it
        on every fit, and each call would be two eager dispatches)."""
        m = getattr(self, "_mask", None)
        if m is None:
            pn = self.padded_n
            m = (jnp.arange(pn) < self._n).astype(jnp.float32)
            self._mask = m
        return m

    def items(self) -> List[Any]:
        if self._items is not None:
            return self._items
        if self._groups is not None:
            return self._group_items()
        with span("workflow.to_items", n=self._n):
            arrs = self.array()
            host = jax.tree_util.tree_map(np.asarray, arrs)
            return [
                jax.tree_util.tree_map(lambda a, i=i: a[i], host)
                for i in range(self._n)
            ]

    def uniform_array(self) -> Optional[Any]:
        """The items as one array with a leading item axis when every item
        is an array of one shape and dtype, else None (ragged items, or
        items that are not single arrays). Host items cost one
        ``np.stack`` and one put, and that array is kept: a second node
        fed by this same ``Dataset`` finds it and uploads nothing. Device
        items cost one ``jnp.stack``, not kept (it would hold a second
        copy of data the device already has)."""
        if self._uploaded is not None:
            return self._uploaded
        if self._groups is not None:
            return self._joined()
        items = self.items()
        keys = {
            (x.shape, str(x.dtype))
            if hasattr(x, "shape") and hasattr(x, "dtype")
            else None
            for x in items
        }
        if len(keys) != 1 or None in keys:
            return None
        h2d = HostPuts()
        if any(isinstance(x, jax.Array) for x in items):
            stacked = jnp.stack([h2d.asarray(x) for x in items])
            h2d.count(h2d.puts)  # a put for each item still on the host
            return stacked
        self._uploaded = h2d.asarray(np.stack(items))
        h2d.count(len(items))
        return self._uploaded

    def __iter__(self):
        return iter(self.items())

    def first(self) -> Any:
        if self._items is not None:
            return self._items[0]
        if self._groups is not None:
            for places, batch in self.groups():
                at = np.flatnonzero(np.asarray(places) == 0)
                if len(at):
                    return jax.tree_util.tree_map(
                        lambda a: a[int(at[0])], batch)
        return jax.tree_util.tree_map(lambda a: a[0], self.array())

    def take(self, k: int) -> List[Any]:
        return self.items()[:k]

    # -- conversions -------------------------------------------------------

    def to_array_mode(self) -> "Dataset":
        if self.is_array:
            return self
        if self._groups is not None:
            joined = self._joined()
            if joined is None:
                raise ValueError(
                    "shape groups of several shapes have no one array"
                )
            return Dataset(arrays=joined, n=self._n)
        if self.is_host:
            # materializes the WHOLE feature matrix in HBM — the thing
            # host-blocks mode exists to avoid; legitimate only for
            # small datasets (tests, cross-checks)
            full = jnp.concatenate(
                [jnp.asarray(b) for b in self._host_blocks], axis=1
            )
            return Dataset(arrays=full, n=self._n)
        with span("workflow.to_array", n=self._n):
            h2d = HostPuts()
            host_items = sum(
                any(
                    not isinstance(leaf, jax.Array)
                    for leaf in jax.tree_util.tree_leaves(x)
                )
                for x in self._items
            )
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack([h2d.asarray(x) for x in xs]),
                *self._items,
            )
            h2d.count(host_items)
        return Dataset(arrays=stacked, n=self._n)

    # -- transforms (eager; graph-level laziness lives in Expressions) -----

    def map(self, fn: Callable[[Any], Any]) -> "Dataset":
        """Per-example host map (items mode result)."""
        with span("workflow.map_items", n=self._n):
            return Dataset(items=[fn(x) for x in self.items()])

    def map_arrays(self, fn: Callable[[Any], Any]) -> "Dataset":
        """Whole-batch array transform; ``fn`` must preserve the leading axis
        and map zero pad rows to values safe to keep as padding."""
        return Dataset(arrays=fn(self.padded()), n=self._n)

    def flat_map(self, fn: Callable[[Any], Sequence[Any]]) -> "Dataset":
        out: List[Any] = []
        for x in self.items():
            out.extend(fn(x))
        return Dataset(items=out)

    def filter(self, pred: Callable[[Any], bool]) -> "Dataset":
        return Dataset(items=[x for x in self.items() if pred(x)])

    def zip(self, other: "Dataset") -> "Dataset":
        if self.n != other.n:
            raise ValueError(f"zip length mismatch: {self.n} vs {other.n}")
        if self.is_array and other.is_array:
            pn = max(self.padded_n, other.padded_n)
            a = self._pad_to(pn)._arrays
            b = other._pad_to(pn)._arrays
            return Dataset(arrays=(a, b), n=self.n)
        return Dataset(
            items=list(zip(self.items(), other.items()))
        )

    def _pad_to(self, pn: int) -> "Dataset":
        arrs = self.to_array_mode()._arrays
        cur = _leading_dim(arrs)
        if cur == pn:
            return self.to_array_mode()
        if cur > pn:
            raise ValueError("cannot shrink padding")
        pad = pn - cur
        padded = jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
            ),
            arrs,
        )
        return Dataset(arrays=padded, n=self._n)

    # -- placement ---------------------------------------------------------

    def shard(self, mesh=None) -> "Dataset":
        """Pad to a multiple of the data-shard count and place the leading
        axis over the mesh's data axis."""
        mesh = mesh or mesh_lib.current_mesh()
        nshards = mesh.shape[mesh_lib.DATA_AXIS]
        ds = self.to_array_mode()
        pn = -(-ds.padded_n // nshards) * nshards
        ds = ds._pad_to(pn)
        sharded = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a, mesh_lib.data_sharding(mesh, ndim=a.ndim)
            ),
            ds._arrays,
        )
        return Dataset(arrays=sharded, n=self._n)

    def cache(self) -> "Dataset":
        """Materialize device buffers now (reference: Cacher / rdd.cache)."""
        if self.is_array:
            jax.block_until_ready(self._arrays)
        elif self._groups is not None:
            jax.block_until_ready([b for _, b in self.groups()])
        self._cached = True
        return self

    @property
    def is_cached(self) -> bool:
        return self._cached

    # -- shape groups ------------------------------------------------------

    def grouped(self) -> Optional["Dataset"]:
        """This data set as shape groups: itself if it is one, its one
        array as the one group, its items grouped by shape and dtype with
        a ``np.stack`` and a put a group for those on the host (kept, as
        ``uniform_array`` keeps its upload). None where an item is not a
        single array."""
        if self._groups is not None:
            return self
        if self.is_array:
            x = self.array()
            if not hasattr(x, "shape"):
                return None
            return Dataset.from_groups([(np.arange(self._n), x)], n=self._n)
        if self.is_host:
            return None
        if self._uploaded is not None:
            return Dataset.from_groups(
                [(np.arange(self._n), self._uploaded)], n=self._n)
        if self._grouped is not None:
            return self._grouped
        by_shape: dict = {}
        for i, x in enumerate(self._items):
            if not (hasattr(x, "shape") and hasattr(x, "dtype")):
                return None
            by_shape.setdefault((x.shape, str(x.dtype)), []).append(i)
        h2d, host_items, groups = HostPuts(), 0, []
        for places in by_shape.values():
            rows = [self._items[i] for i in places]
            if any(isinstance(x, jax.Array) for x in rows):
                host_items += sum(not isinstance(x, jax.Array) for x in rows)
                batch = jnp.stack([h2d.asarray(x) for x in rows])
            else:
                host_items += len(rows)
                batch = h2d.asarray(np.stack(rows))
            groups.append((np.asarray(places), batch))
        h2d.count(host_items)
        self._grouped = Dataset.from_groups(groups, n=self._n)
        return self._grouped

    def then(self, fn: Any, arrays: Any) -> "Dataset":
        """These shape groups with one more per-row function noted
        (``Transformer.rowwise``'s ``fn(arrays, batch)``); nothing runs
        until the rows are asked for."""
        return Dataset(
            groups=self._groups, steps=self._steps + ((fn, arrays),),
            n=self._n,
        )

    def group_rows(self) -> List[tuple]:
        """[(places, shapes of one row)] of each group once the noted
        functions have run, by ``jax.eval_shape`` alone."""
        out = []
        for places, batch in self._groups:
            one = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct((1,) + a.shape[1:], a.dtype),
                batch)
            if self._steps:
                fns, arrays = zip(*self._steps)
                one = chunks_lib.account_of(fns, arrays, batch)[1]
            out.append((places, one))
        return out

    def _parts(self, batch: Any, keep: bool) -> tuple:
        """(rows a part, shapes of one row of the result, rows each
        program computes beyond the group's own, the (start, rows through
        the noted functions) of each chunk as they are asked for) for one
        group: the chunk's rows from bytes and kept a shape
        (``chunks.planned_rows``; ``keep`` where the group's whole result
        stays on the device), a group shorter than the chunk filled up to
        it, one program per (function, shape, chunk), span
        ``workflow.groups.chunk`` a chunk."""
        fns, arrays = zip(*self._steps) if self._steps else ((), ())
        chunk, one = chunks_lib.planned_rows(fns, arrays, batch, keep)
        rows = _leading_dim(batch)
        starts = chunks_lib.chunk_starts(rows, min(chunk, rows))

        def run():
            # two chunks in flight and no more: the host would dispatch a
            # whole group ahead, and a chunk's outputs are allocated when
            # it is dispatched (a fit's peak on a v5e read 13.8 GB with
            # the host held back by compiling and up to 15.4 of 16.9
            # without: PERF.md section 5, PR 37)
            ahead = None
            for start in starts:
                with span("workflow.groups.chunk", n=chunk):
                    part = chunks_lib.take_chunk(
                        fns, chunk, arrays, batch, start)
                    if ahead is not None:
                        jax.block_until_ready(ahead)
                    ahead = part
                    yield start, part

        return min(chunk, rows), one, len(starts) * chunk - rows, run()

    def chunks(self):
        """(places, rows) a chunk, the noted functions run on it: for a
        reader that keeps a little of each chunk (a sampler) and lets the
        rest go."""
        for places, batch in self._groups:
            chunk, _, _, parts = self._parts(batch, keep=False)
            done = 0
            for start, part in parts:
                new = start + chunk - done  # the last chunk overlaps
                if new < chunk:
                    part = jax.tree_util.tree_map(
                        lambda a: a[chunk - new:], part)
                yield places[done:done + new], part
                done += new

    def groups(self) -> List[tuple]:
        """[(places, array)], the noted functions run: each group through
        all of them a chunk of rows at a time, written into the group's
        result in place. Span ``workflow.groups`` once; the chunk counters
        of ``count_chunked``."""
        if not self._steps:
            return self._groups
        out, programs, twice = [], 0, 0
        with span("workflow.groups", n=self._n, groups=len(self._groups)):
            for places, batch in self._groups:
                rows = len(places)
                chunk, one, more, parts = self._parts(batch, keep=True)
                if chunk == rows:
                    res = next(parts)[1]
                else:
                    res = jax.tree_util.tree_map(
                        lambda a: jnp.zeros((rows,) + a.shape[1:], a.dtype),
                        one)
                    for start, part in parts:
                        res = chunks_lib._write_rows(res, part, start)
                out.append((places, res))
                programs += len(chunks_lib.chunk_starts(rows, chunk))
                twice += more
        count_chunked(self._n, programs, twice, self._n, len(out))
        self._groups, self._steps = out, ()
        return out

    def _joined(self) -> Optional[Any]:
        """The groups' rows as one array in the data set's order, where
        they are all of one shape; else None. One concatenate and one
        gather, none where the one group is in order already."""
        if self._uploaded is not None:
            return self._uploaded
        groups = self.groups()
        keys = {
            chunks_lib.shape_key(b, lambda a: a.shape[1:]) for _, b in groups
        }
        if len(keys) != 1:
            return None
        places = np.concatenate([np.asarray(p) for p, _ in groups])
        joined = groups[0][1] if len(groups) == 1 else (
            jax.tree_util.tree_map(
                lambda *parts: jnp.concatenate(parts),
                *[b for _, b in groups]))
        if not np.array_equal(places, np.arange(len(places))):
            order = jnp.asarray(np.argsort(places))
            joined = jax.tree_util.tree_map(
                lambda a: jnp.take(a, order, axis=0), joined)
        self._uploaded = joined
        return joined

    def _group_items(self) -> List[Any]:
        """The groups cut into items, a slice each (and counted so)."""
        out: List[Any] = [None] * self._n
        with span("workflow.to_items", n=self._n):
            for places, batch in self.groups():
                for j, i in enumerate(places):
                    out[int(i)] = jax.tree_util.tree_map(
                        lambda a, j=j: a[j], batch)
        count_chunked(self._n, 0, 0, 0)
        return out

    def __repr__(self) -> str:
        if self._groups is not None:
            return (
                f"Dataset(groups={len(self._groups)}, n={self._n}, "
                f"noted={len(self._steps)})"
            )
        if self.is_host:
            return (
                f"Dataset(host_blocks, n={self._n}, "
                f"widths={self.block_widths})"
            )
        if self.is_array:
            shapes = jax.tree_util.tree_map(
                lambda a: tuple(a.shape), self._arrays
            )
            return f"Dataset(array, n={self._n}, shapes={shapes})"
        return f"Dataset(items, n={self._n})"
