"""Rows through per-row functions a chunk at a time.

The byte account and the chunk programs that ``workflow.api.RowwiseRun``
(one array) and a shape-grouped ``Dataset`` (one array a shape) share. A
*function* here is what ``Transformer.rowwise()`` hands out:
``fn(arrays, batch)`` maps row i of ``batch`` to row i of its result,
looks at no other row, and hashes by its settings, so that nodes of equal
settings share compiled programs whatever arrays they hold.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def leading_dim(tree: Any) -> int:
    # a BCOO (or any array-like) IS the array — don't descend into its
    # pytree leaves (a BCOO's first leaf is the nse-length values array)
    if hasattr(tree, "shape"):
        return tree.shape[0]
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty pytree")
    return leaves[0].shape[0]


def device_free_bytes(batch: Any) -> Optional[int]:
    """What the allocator of the device that holds ``batch`` could still
    hand out: its limit less what is live. None where the backend keeps
    no such account (the CPU)."""
    device = next(iter(jax.tree_util.tree_leaves(batch)[0].devices()))
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats or "bytes_in_use" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def tree_bytes(tree: Any) -> int:
    return sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree)
    )


def shape_key(tree: Any, shape: Callable) -> tuple:
    """A pytree of arrays as a hashable (structure, shapes and dtypes)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        jax.ShapeDtypeStruct(shape(a), a.dtype) for a in leaves
    )


@lru_cache(maxsize=64)
def row_account(fns: tuple, arrays_key: tuple, one_key: tuple) -> tuple:
    """(the bytes one row holds across the functions, the shapes of one
    row of the last one's result), by ``jax.eval_shape``. Kept by the
    functions and the shapes: a fit plans the same run again, and
    tracing a folded function takes the host tens of milliseconds in
    which the chip has nothing to do."""
    arrays = jax.tree_util.tree_unflatten(*arrays_key)
    one = jax.tree_util.tree_unflatten(*one_key)
    item_bytes = 0
    for fn, arr in zip(fns, arrays):
        if hasattr(fn, "held"):
            item_bytes += tree_bytes(jax.eval_shape(fn.held, arr, one))
        one = jax.eval_shape(fn, arr, one)
        item_bytes += tree_bytes(one)
    return item_bytes, one


def account_of(fns, arrays, batch) -> tuple:
    """``row_account`` of concrete arrays and one row of ``batch``."""
    return row_account(
        tuple(fns),
        shape_key(tuple(arrays), lambda a: a.shape),
        shape_key(batch, lambda a: (1,) + a.shape[1:]),
    )


def rows_that_fit(
    rows: int, item_bytes: int, out_bytes: int, free_bytes: Optional[int],
    most_rows: Optional[int] = None,
) -> int:
    """How many of ``rows`` a program may take at once: what one row
    holds across a run against half of what the device has free once the
    joined result is taken out (the other half is the compiler's: a
    program's temporaries are not in the shapes), at most ``most_rows``
    where the caller gives a cap, and a power of two where that is fewer
    than ``rows``, so that a little more or less free memory plans the
    same program. All of ``rows`` where the batch fits whole, or where
    the backend gives no account of its memory and no cap is given."""
    fit = rows
    if free_bytes is not None:
        budget = (free_bytes - out_bytes) // 2
        if rows * item_bytes > budget:
            fit = max(budget // max(item_bytes, 1), 1)
    if most_rows is not None:
        fit = min(fit, max(most_rows, 1))
    return rows if fit >= rows else 1 << (int(fit).bit_length() - 1)


def rows_a_chunk(
    rows: int, item_bytes: int, out_bytes: int, free_bytes: Optional[int],
) -> int:
    """Rows a chunk of one batch from bytes: the batch divided evenly
    over the chunks that ``rows_that_fit`` asks for."""
    chunks = -(-rows // rows_that_fit(rows, item_bytes, out_bytes, free_bytes))
    return -(-rows // chunks)


def chunk_starts(rows: int, chunk: int) -> list:
    """Where the chunks of ``chunk`` rows start: the last one
    ``chunk`` before the end, so that all share one program and the rows
    computed twice are fewer than there are chunks."""
    return list(range(0, rows - chunk, chunk)) + [rows - chunk]


def valid_rows(part, start, n):
    """``part`` with the rows at ``start`` + i >= ``n`` zeroed (the
    Dataset's padding rule)."""
    valid = start + jnp.arange(leading_dim(part)) < n
    return jax.tree_util.tree_map(
        lambda r: jnp.where(
            valid.reshape((-1,) + (1,) * (r.ndim - 1)), r, 0
        ),
        part,
    )


def _through(fns, arrays, part):
    for fn, arr in zip(fns, arrays):
        part = fn(arr, part)
    return part


def _rows_at(batch, start, chunk_rows):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk_rows), batch
    )


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _run_chunk(fns, chunk_rows, arrays, out, batch, start, n):
    """One chunk of a run: rows [start, start + chunk_rows) of ``batch``
    through every function of the run, rows past ``n`` zeroed, written
    into ``out`` in place."""
    part = _through(fns, arrays, _rows_at(batch, start, chunk_rows))
    return jax.tree_util.tree_map(
        lambda o, r: jax.lax.dynamic_update_slice_in_dim(
            o, r.astype(o.dtype), start, 0
        ),
        out, valid_rows(part, start, n),
    )


# -- shape groups: a program a function, so that runs share them ----------
#
# A RowwiseRun's chunk is one program (``_run_chunk``). A shape group's
# chunk is one program a function: dense SIFT at one image shape takes
# the TPU compiler ten to thirty seconds, a fit has it in two runs (under
# the PCA's sampler and under the projection) and a fitted predictor in a
# third, and a program per run compiled it once for each (365 s of
# compiling before a first fit, measured on a v5e, PR 37).


@partial(jax.jit, static_argnums=(2,))
def _rows_of(batch, start, chunk_rows):
    return _rows_at(batch, start, chunk_rows)


@lru_cache(maxsize=None)
def rows_program(fn):
    """``fn`` as a jitted program of its own, named after it: a trace's
    module line then says which node a program was (``jit_rows_SiftRows``,
    ``jit_rows_project_columns``, ...)."""
    def rows(arr, part):
        return fn(arr, part)

    name = getattr(fn, "__name__", type(fn).__name__).strip("_")
    rows.__name__ = rows.__qualname__ = "rows_" + name
    return jax.jit(rows)


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(out, part, start):
    return jax.tree_util.tree_map(
        lambda o, r: jax.lax.dynamic_update_slice_in_dim(
            o, r.astype(o.dtype), start, 0
        ),
        out, part,
    )


@partial(jax.jit, static_argnums=(1,))
def _filled_up(batch, chunk_rows):
    """``batch`` with its last row repeated up to ``chunk_rows`` rows."""
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(
            a, ((0, chunk_rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1),
            mode="edge"),
        batch)


def take_chunk(fns, chunk_rows, arrays, batch, start):
    """Rows [start, start + chunk_rows) of a shape group's ``batch``
    through every function noted for it, a program a function. A group
    of fewer rows than the chunk is filled up to it with its last row,
    so that it runs the programs the shape's other groups compiled, and
    only its own rows come back."""
    rows = leading_dim(batch)
    part = batch
    if chunk_rows < rows:
        part = _rows_of(batch, start, chunk_rows)
    elif chunk_rows > rows:
        part = _filled_up(batch, chunk_rows)
    for fn, arr in zip(fns, arrays):
        part = rows_program(fn)(arr, part)
    if chunk_rows > rows:
        part = jax.tree_util.tree_map(lambda a: a[:rows], part)
    return part


# (functions, shapes of one row, keep) -> (rows a chunk, the most rows a
# group of the shape has had): a shape's chunk
_planned: dict = {}

# What a shape group's chunk program may hold in flight, rows in and every
# function's output: a dense-SIFT program over 24 and more images of
# 500 x 375 gave wrong descriptors for some of them, and once halted the
# core, where 16 were right image for image (v5e, PR 37, PERF.md section
# 6; by the TPU compiler's own account the program at 16 images holds
# 1.71 GB of temporaries beside 0.61 GB of output, at 24 1.94 beside
# 0.91). The cause is not found: the cap keeps the programs on the side
# that was measured right, and ``chip_smoke.py``'s ``chunks`` phase holds
# the planned chunk against the same images four at a time.
PROGRAM_BYTES = 1 << 31


@jax.jit
def _after_the_queue(x):
    return x + 1


def planned_rows(fns, arrays, batch, keep: bool) -> tuple:
    """(rows a chunk, shapes of one row of the result) for a shape group
    through ``fns``; ``keep`` where the whole group's result stays on the
    device. Planned from bytes (``rows_that_fit``, and no chunk over
    ``PROGRAM_BYTES`` of rows in flight) the first time rows of these
    shapes come through these functions, once the device has finished
    what it had queued (chunks dispatched ahead hold memory that is about
    to come back), and kept: every later group of the shape — the next
    fit's, a fitted predictor's, a held-out set's few images — runs the
    programs that chunk compiled, a short group filled up to it and a
    long one's last chunk starting a chunk before its end. Only a group
    of more rows than the shape has had plans again, and only upwards.
    What is free at a later moment does not: a plan that followed it
    compiled new programs inside a timed fit, because the bytes in use
    at that moment count the chunks the host has dispatched ahead (v5e,
    PR 37, PERF.md section 6)."""
    rows = leading_dim(batch)
    item_bytes, one = account_of(fns, arrays, batch)
    key = (tuple(fns), shape_key(batch, lambda a: a.shape[1:]), keep)
    chunk, most = _planned.get(key, (0, 0))
    if rows > most:
        leaf = jax.tree_util.tree_leaves(batch)[0]
        jax.block_until_ready(_after_the_queue(
            jax.device_put(np.float32(0), next(iter(leaf.devices())))))
        in_flight = item_bytes + tree_bytes(batch) // max(rows, 1)
        chunk = max(chunk, rows_that_fit(
            rows, item_bytes, rows * tree_bytes(one) if keep else 0,
            device_free_bytes(batch),
            most_rows=PROGRAM_BYTES // max(in_flight, 1)))
        _planned[key] = (chunk, rows)
    return chunk, one
