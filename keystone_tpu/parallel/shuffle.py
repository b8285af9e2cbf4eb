"""Device-side shuffle / repartition via ``lax.all_to_all`` under shard_map.

Reference: the Spark shuffle behind ``Shuffler`` (nodes/util/Shuffler.scala,
repartition) and the HashPartitioner ``groupBy`` the per-class solvers used
(BlockWeightedLeastSquaresEstimator.scala groupByClasses). On TPU a shuffle
is not a runtime service but ONE collective: each shard packs its rows into
fixed-capacity per-destination buckets, a single ``lax.all_to_all`` rides
the ICI, and receivers unpack. Static shapes require the MoE router's
capacity-factor discipline — per-(src, dst) buckets have a fixed capacity,
overflow rows are dropped and *counted* (callers size capacity so the count
is provably zero; `device_shuffle`'s slot-exact routing needs no slack).

Memory: the packed buffer is ``(n_shards, capacity, ...)`` per shard, so
capacity should be ~rows_per_shard / n_shards for balanced exchanges (or
rows_per_shard for worst-case-skew guarantees).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from keystone_tpu.parallel import mesh as mesh_lib


def _pack_buckets(payload, dest, n_shards: int, capacity: int):
    """Pack rows into per-destination buckets on one shard.

    ``payload`` is a tuple of arrays sharing their leading dim; ``dest`` is
    an int32 row destination in ``[0, n_shards)`` — or ``>= n_shards`` to
    discard the row (pad rows). Returns bucket tree ``(n_shards, capacity,
    ...)``, validity mask ``(n_shards, capacity)``, and the number of
    non-discarded rows that overflowed their bucket.
    """
    m = dest.shape[0]
    sentinel = n_shards
    d = jnp.where(dest < n_shards, dest, sentinel).astype(jnp.int32)
    counts = jax.ops.segment_sum(
        jnp.ones((m,), jnp.int32), d, num_segments=n_shards + 1
    )
    offsets = jnp.cumsum(counts) - counts  # (n_shards + 1,)
    order = jnp.argsort(d, stable=True)
    ds = d[order]
    pos = jnp.arange(m, dtype=jnp.int32) - offsets[ds]
    keep = (ds < n_shards) & (pos < capacity)
    row_idx = jnp.where(keep, ds, n_shards)  # OOB => dropped by scatter
    slot = jnp.where(keep, pos, capacity)

    def pack(x):
        xs = jnp.take(x, order, axis=0)
        buf = jnp.zeros((n_shards, capacity) + x.shape[1:], x.dtype)
        return buf.at[row_idx, slot].set(xs, mode="drop")

    buckets = jax.tree_util.tree_map(pack, payload)
    valid = jnp.zeros((n_shards, capacity), jnp.int32)
    valid = valid.at[row_idx, slot].set(1, mode="drop")
    overflowed = jnp.sum(counts[:n_shards]) - jnp.sum(valid)
    return buckets, valid, overflowed


def all_to_all_repartition(
    payload,
    dest: jnp.ndarray,
    capacity: int,
    mesh=None,
) -> Tuple[tuple, jnp.ndarray, jnp.ndarray]:
    """Route rows of a data-sharded array (tree) to the shard named per-row.

    ``payload``: tuple of arrays with a common sharded leading (example)
    axis. ``dest``: per-row destination shard id (>= n_shards discards the
    row). Each shard returns ``(n_shards * capacity, ...)`` received rows
    (source-major), an int32 validity mask, and the global overflow count
    (replicated scalar) — ``0`` when ``capacity`` was sufficient.
    """
    mesh = mesh or mesh_lib.current_mesh()
    axes = mesh_lib._example_axes(mesh)
    n_shards = mesh_lib.n_data_shards(mesh)

    row_spec = lambda x: P(axes, *([None] * (x.ndim - 1)))
    in_specs = (
        jax.tree_util.tree_map(row_spec, payload),
        P(axes),
    )
    out_specs = (
        jax.tree_util.tree_map(row_spec, payload),
        P(axes),
        P(),
    )

    @partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    def exchange(local_payload, local_dest):
        buckets, valid, over = _pack_buckets(
            local_payload, local_dest, n_shards, capacity
        )
        swap = lambda b: jax.lax.all_to_all(
            b, axes, split_axis=0, concat_axis=0, tiled=True
        )
        recv = jax.tree_util.tree_map(swap, buckets)
        recv_valid = swap(valid)
        total_over = jax.lax.psum(over, axes)
        flat = jax.tree_util.tree_map(
            lambda b: b.reshape((n_shards * capacity,) + b.shape[2:]), recv
        )
        return flat, recv_valid.reshape(-1), total_over[None]

    out, valid, over = exchange(payload, dest.astype(jnp.int32))
    return out, valid, over[0]


def repartition_by_key(
    payload, keys: jnp.ndarray, capacity: int, mesh=None
):
    """Hash-partition rows onto shards by ``key % n_shards`` — the
    HashPartitioner ``groupBy`` analogue (negative keys discard)."""
    mesh = mesh or mesh_lib.current_mesh()
    n_shards = mesh_lib.n_data_shards(mesh)
    dest = jnp.where(keys >= 0, keys % n_shards, n_shards)
    return all_to_all_repartition(payload, dest, capacity, mesh)


def device_shuffle(
    x: jnp.ndarray,
    n: int,
    seed: int = 0,
    mesh=None,
) -> jnp.ndarray:
    """Exact random permutation of the first ``n`` (valid) rows of a padded
    row-sharded array, entirely on device: ``out[j] = x[perm[j]]`` with
    ``perm = default_rng(seed).permutation(n)`` — bit-identical to the
    host-side ``Shuffler`` path. Every row is routed to its permuted global
    slot (destination shard + local slot payload) in ONE all_to_all; pad
    rows stay zero.
    """
    mesh = mesh or mesh_lib.current_mesh()
    n_shards = mesh_lib.n_data_shards(mesh)
    n_pad = x.shape[0]
    if n_pad % n_shards:
        raise ValueError(f"padded rows {n_pad} not divisible by {n_shards}")
    rows_per_shard = n_pad // n_shards

    perm = np.random.default_rng(seed).permutation(n)
    inv = np.argsort(perm)  # row g lands at out slot inv[g]
    target = np.full((n_pad,), n_pad, np.int32)  # pad rows -> discard
    target[:n] = inv
    dest_h = np.where(target < n_pad, target // rows_per_shard, n_shards)

    # The permutation is known host-side, so size the per-(src, dst)
    # buckets at their exact max occupancy (~rows_per_shard / n_shards
    # for a random perm) — never rows_per_shard, which would materialize
    # a global-size buffer on every shard and defeat the sharding.
    src = np.arange(n_pad) // rows_per_shard
    pair_counts = np.zeros((n_shards, n_shards + 1), np.int64)
    np.add.at(pair_counts, (src, dest_h), 1)
    capacity = max(int(pair_counts[:, :n_shards].max()), 1)

    dest = jnp.asarray(dest_h.astype(np.int32))
    slot = jnp.asarray((target % rows_per_shard).astype(np.int32))
    (rows, slots), valid, over = all_to_all_repartition(
        (x, slot), dest, capacity, mesh
    )

    axes = mesh_lib._example_axes(mesh)
    row_spec = P(axes, *([None] * (x.ndim - 1)))

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(row_spec, P(axes), P(axes)),
        out_specs=row_spec,
        check_vma=False,
    )
    def place(rows, slots, valid):
        idx = jnp.where(valid > 0, slots, rows_per_shard)  # OOB => drop
        out = jnp.zeros((rows_per_shard,) + rows.shape[1:], rows.dtype)
        return out.at[idx].set(rows, mode="drop")

    out = place(rows, slots, valid)
    # Capacity above is exact only under contiguous block sharding of the
    # example axis; if that assumption is ever violated, fail loudly
    # instead of silently zeroing dropped rows. The scalar sync happens
    # AFTER place() is dispatched, so it doesn't stall the async stream
    # mid-pipeline.
    over_count = int(over)
    if over_count:
        raise RuntimeError(
            f"device_shuffle dropped {over_count} rows: the input's example"
            " axis is not contiguously block-sharded over the mesh"
        )
    return out
