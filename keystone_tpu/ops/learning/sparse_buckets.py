"""Sparse products by destination bucket: ``out[dst] += val · table[src]``
over a sparse matrix's entries, as one Pallas TPU kernel with no gather
or scatter outside a vector register.

The entries are laid out once (``bucket_layout``): cut into chunks of at
most _SORT_CHUNK, each sorted by (destination bucket, source), where a
bucket is BUCKET consecutive destinations. X·w takes destination = row and
source = column; Xᵀ·r the other way round. The kernel walks the entries
a vector register (8 × 128) at a time, with the source table and the
output both whole in VMEM:

- the sources: the table's 128-wide windows that the register's sources
  fall in are read and selected by lane (``take_along_axis``, a lane
  gather within one register), UNROLL windows a loop step; the sources
  are sorted within a bucket, so a register's run over the table's
  windows is short;
- the destinations: the register's products are added into BUCKET
  accumulator registers, one for each destination of the current
  bucket, by compare and select; when the bucket changes the
  accumulators are summed, transposed into 128-wide rows and added to
  the output.

So no update ever waits on another to the same hot destination, and
every sum is a float32 sum of float32 products. ``bucket_meta`` gives
each register its bucket range and the window ranges of its sources (of
its first bucket's entries and of the rest), read from SMEM as loop
bounds."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.images.pallas_kernels import auto_interpret

LANES = 128
SUBLANES = 8
REG = LANES * SUBLANES  # entries a vector register
STEP_REGS = 128  # registers a grid step (the SMEM metadata block's width)
STEP = STEP_REGS * REG  # entries a grid step: the padding unit
_SORT_CHUNK = 1 << 24  # entries at most a sort of the layout
UNROLL = 16  # source windows a step of the kernel's window loop
BUCKET = 256  # destinations a bucket: a multiple of 128 that divides REG
_BIG = 1 << 30
MAX_VMEM = 96 << 20  # what the kernel may ask of a core's VMEM (v5e: 128 MiB)


def padded(e: int) -> tuple:
    """(padded length, sort chunk) of e entries: chunks of at most
    _SORT_CHUNK, each a whole number of grid steps."""
    chunks = max(1, -(-e // _SORT_CHUNK))
    chunk = -(-max(1, -(-e // chunks)) // STEP) * STEP
    return chunks * chunk, chunk


def table_len(n: int) -> int:
    """A vector of n padded to whole (8, 128) tiles."""
    return -(-n // REG) * REG


def _vmem_bytes(n_src: int, n_dst: int) -> int:
    """The kernel's VMEM: the source table (two buffers), the output,
    the accumulators and the entry blocks (two buffers of three)."""
    return 4 * (2 * table_len(n_src) + table_len(n_dst) + BUCKET * REG
                + 6 * STEP)


def fits(n: int, d: int) -> bool:
    """Whether both products of an (n, d) matrix fit the kernel's VMEM."""
    return max(_vmem_bytes(n, d), _vmem_bytes(d, n)) <= MAX_VMEM


@jax.tree_util.register_dataclass
@dataclasses.dataclass(eq=False)
class BucketLayout:
    """Entries sorted by (destination bucket, source) within chunks:
    ``src``, ``dst`` (int32) and ``val`` (float32), each (E_p / 128,
    128); ``meta`` (8, E_p / 1024) int32 — a register's first and last
    bucket, the first and last source window of its first bucket's
    entries and of the rest (empty as (2^30, −1)). Padding adds 0 to
    the last destination."""

    src: Any
    dst: Any
    val: Any
    meta: Any


def _slice(a, i, size: int, e: int, fill, col: Optional[int] = None):
    """Entries [i·size, (i+1)·size) of the first e of ``a`` ((E,), or
    column ``col`` of (E, 2)), ``fill`` past e; no copy of ``a``."""
    if col is not None and e < size:
        a = a[:, col]
        col = None
    if e < size:
        return jnp.pad(a, (0, size - e), constant_values=fill)
    start = i * size
    src = jnp.minimum(start, e - size)
    if col is None:
        part = jax.lax.dynamic_slice(a, (src,), (size,))
    else:
        part = jax.lax.dynamic_slice(a, (src, col), (size, 1))[:, 0]
    part = jnp.roll(part, src - start)
    return jnp.where(jnp.arange(size) < e - start, part, fill)


def bucket_meta(src, dst):
    """``BucketLayout.meta`` of sorted sources and destinations, each
    (R, 128) with R a whole number of registers."""
    w = src.reshape(-1, REG) >> 7
    b = dst.reshape(-1, REG) // BUCKET
    blo, bhi = b.min(1), b.max(1)
    first = b == blo[:, None]
    return jnp.stack([
        blo, bhi,
        jnp.where(first, w, _BIG).min(1), jnp.where(first, w, -1).max(1),
        jnp.where(first, _BIG, w).min(1), jnp.where(first, -1, w).max(1),
        blo, blo,
    ]).astype(jnp.int32)


def bucket_layout(values, indices, dst_col: int, n_dst: int,
                  n_src: int) -> BucketLayout:
    """The layout of a sparse matrix's E entries (``values`` (E,),
    ``indices`` (E, 2)) with destination ``indices[:, dst_col]`` (of
    ``n_dst``) and source the other column (of ``n_src``). Each chunk
    is sorted and tiled in the loop, so that the (E_p / 128, 128)
    arrays are its outputs stacked, with no copy of them."""
    e = values.shape[0]
    ep, chunk = padded(e)
    dst_fill = table_len(n_dst) - 1
    sbits = max(1, (table_len(n_src) - 1).bit_length())
    bbits = max(1, (table_len(n_dst) // BUCKET - 1).bit_length())

    def sort_chunk(i):
        d = _slice(indices, i, chunk, e, dst_fill, col=dst_col)
        s = _slice(indices, i, chunk, e, 0, col=1 - dst_col)
        v = _slice(values.astype(jnp.float32), i, chunk, e, 0.0)
        if sbits + bbits <= 32:
            key = ((d // BUCKET).astype(jnp.uint32) << sbits) | s.astype(
                jnp.uint32)
            key, d, v = jax.lax.sort((key, d, v), num_keys=1)
            s = (key & jnp.uint32((1 << sbits) - 1)).astype(jnp.int32)
        else:
            _, s, d, v = jax.lax.sort((d // BUCKET, s, d, v), num_keys=2)
        s, d, v = (a.reshape(-1, LANES) for a in (s, d, v))
        return s, d, v, bucket_meta(s, d)

    s, d, v, meta = jax.lax.map(sort_chunk, jnp.arange(ep // chunk))
    tiles = (lambda a: a.reshape(-1, LANES))
    return BucketLayout(src=tiles(s), dst=tiles(d), val=tiles(v),
                        meta=jnp.moveaxis(meta, 0, 1).reshape(8, -1))


def _kernel(meta_ref, src_ref, dst_ref, val_ref, tbl_ref, out_hbm,
            out_ref, acc_ref, cur_ref, sem):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cur_ref[0] = meta_ref[0, 0]

    def flush():
        # acc[j] holds bucket cur's destination j spread over a register
        for q in range(BUCKET // LANES):
            sums = jnp.sum(acc_ref[pl.ds(q * LANES, LANES)], axis=1)
            row = jnp.sum(sums.T, axis=0, keepdims=True)  # (1, 128)
            at = pl.ds(cur_ref[0] * (BUCKET // LANES) + q, 1)
            out_ref[at, :] = out_ref[at, :] + row
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def register(k, carry):
        rows = pl.ds(k * SUBLANES, SUBLANES)
        s, d, v = src_ref[rows, :], dst_ref[rows, :], val_ref[rows, :]
        window, lane = s >> 7, s & (LANES - 1)

        def windows(lo, hi, ps):
            # UNROLL windows a step into as many results, so that their
            # loads and lane gathers do not wait on one another
            def step(i, ps):
                out = []
                for u, got in enumerate(ps):
                    w = lo + i * UNROLL + u
                    row = tbl_ref[pl.ds(jnp.minimum(w, hi), 1), :]
                    took = jnp.take_along_axis(
                        jnp.broadcast_to(row, (SUBLANES, LANES)), lane,
                        axis=1, mode="promise_in_bounds")
                    out.append(jnp.where((window == w) & (w <= hi), took,
                                         got))
                return tuple(out)

            steps = jnp.maximum(hi - lo + UNROLL, 0) // UNROLL
            return jax.lax.fori_loop(0, steps, step, ps)

        ps = (jnp.zeros((SUBLANES, LANES), jnp.float32),) * UNROLL
        ps = windows(meta_ref[2, k], meta_ref[3, k], ps)
        ps = windows(meta_ref[4, k], meta_ref[5, k], ps)
        # an entry's window may come in both ranges, with one value
        p = ps[0]
        for got in ps[1:]:
            p = jnp.where(got != 0.0, got, p)
        p = p * v

        def bucket(b, c):
            @pl.when(b != cur_ref[0])
            def _():
                flush()
                cur_ref[0] = b

            at = d - b * BUCKET
            for j in range(BUCKET):
                acc_ref[j] = acc_ref[j] + jnp.where(at == j, p, 0.0)
            return c

        jax.lax.fori_loop(meta_ref[0, k], meta_ref[1, k] + 1, bucket, 0)
        return carry

    jax.lax.fori_loop(0, STEP_REGS, register, 0)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        flush()
        copy = pltpu.make_async_copy(out_ref, out_hbm, sem.at[0])
        copy.start()
        copy.wait()


def bucket_product(layout: BucketLayout, table, n_dst: int, *,
                   interpret: Optional[bool] = None):
    """(n_dst,) float32: ``out[dst] += val · table[src]`` over the
    layout's entries; ``table`` (n_src,) float32."""
    t = table_len(table.shape[0])
    tbl = jnp.pad(table.astype(jnp.float32),
                  (0, t - table.shape[0])).reshape(-1, LANES)
    rows = table_len(n_dst) // LANES
    steps = layout.meta.shape[1] // STEP_REGS
    block = pl.BlockSpec((STEP_REGS * SUBLANES, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((8, STEP_REGS), lambda i: (0, i),
                         memory_space=pltpu.SMEM),
            block, block, block,
            pl.BlockSpec(tbl.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((BUCKET, SUBLANES, LANES), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(t, rows * LANES) + (8 << 20)),
        interpret=auto_interpret(interpret),
    )(layout.meta, layout.src, layout.dst, layout.val, tbl)
    return out.reshape(-1)[:n_dst]
