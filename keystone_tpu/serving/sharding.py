"""Declarative mesh-sharding of fitted-pipeline parameters.

Every serving tier so far scales the *batch*: ``CompiledPipeline``
shards staged rows over the mesh data axis, lanes replicate whole
engines, the fleet replicates whole processes. None of that serves a
model whose parameters exceed one chip's HBM — a replicated lane needs
the full weight set resident per device, so the model axis was the one
direction the stack could not grow.

This module closes it with the pattern the fmengine/EasyLM family uses
for exactly this problem (SNIPPETS.md [2]): a **declarative rule
layer** mapping regex patterns over the fitted pipeline's *named
parameter pytree* to ``PartitionSpec``s, so any fitted pipeline gets a
partitioning without hand-written per-model specs:

- ``named_params`` walks the pipeline's topo-ordered operators and
  extracts every array-valued dataclass field under a stable
  ``"<topo#>/<OpClass>/<field>"`` name — the namespace the rules match
  against (the same fields ``featurize.featurize_token`` hashes, so
  the param set and the model fingerprint can't drift apart);
- ``match_partition_rules(rules, params)`` resolves each named param
  to the first matching rule's spec. Scalars (and one-element arrays)
  always stay replicated — partitioning a scalar is never right.
  Unmatched params raise by default, or fall back to replicated under
  an explicit ``unmatched="replicate"`` — silent partial sharding is
  how "fits on the mesh" claims go quietly wrong;
- ``make_shard_fns`` / ``make_gather_fns`` turn a spec tree into
  per-param placement callables (``device_put`` under a
  ``NamedSharding``), validating divisibility up front — an uneven
  split fails at rule-resolution time with the param's name, not at
  dispatch time inside XLA;
- ``DEFAULT_RULES`` covers the repo's solver outputs: 2-D weight
  matrices (block least-squares ``W``, the dense mappers) split on
  their output/feature-block axis over ``MODEL_AXIS``, biases, means
  and everything else replicated;
- ``ParamBinder`` is the functionalization seam the engine traces
  through: the extracted params become explicit *arguments* of the
  bucket program (placed once, sharded, reused every dispatch) instead
  of baked-in constants, so each device's executable holds only its
  shard of the weights. The binder patches an engine-private copy of
  the pipeline at trace time — the caller's fitted pipeline is never
  touched, and concurrent traces serialize on the binder's lock.

Composition: the spec tree rides a 2-D ``(data, model)`` mesh
(``parallel/mesh.py``), so batch sharding (``shard=``) and model
sharding (``param_sharding=``) are independent axes of the same mesh —
an engine can split rows over ``data`` while splitting weights over
``model``, and XLA inserts the collectives.
"""

from __future__ import annotations

import copy
import dataclasses
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from keystone_tpu.parallel import mesh as mesh_lib

# regex -> PartitionSpec, resolved first-match-wins against the
# "<topo#>/<OpClass>/<field>" param names of ``named_params``
PartitionRules = Sequence[Tuple[str, PartitionSpec]]

# The repo's solver outputs: every fitted linear map stores its weights
# as one (d_in, d_out) / (D, k) matrix named W (BlockLinearMapper,
# LinearMapper, SparseLinearMapper, the demo model's _Affine chain), so the
# output/feature-block axis is the LAST one — split it over MODEL_AXIS;
# biases, intercepts, means, scaler state stay replicated (they are
# k- or D-vectors, noise next to the matrices). The trailing catch-all
# is what makes this a complete default: any fitted pipeline resolves,
# with only its weight matrices actually split.
DEFAULT_RULES: PartitionRules = (
    (r"/W$", PartitionSpec(None, mesh_lib.MODEL_AXIS)),
    (r".*", PartitionSpec()),
)


def _scrub_caches(op) -> None:
    """Remove an operator's underscore-prefixed lazily-attached caches
    (``_vmapped_apply``, ``_arr_digest_cache``, ...) — instance-dict
    entries only; declared underscore-less fields are untouched."""
    d = getattr(op, "__dict__", None)
    if not d:
        return
    for key in [k for k in d if k.startswith("_")]:
        del d[key]


def _is_array(value: Any) -> bool:
    return isinstance(value, (np.ndarray, jax.Array)) or (
        isinstance(value, np.generic)
    )


def _array_fields(op) -> List[Tuple[str, Any]]:
    """The array-valued parameter fields of one operator, in sorted
    field order — the same field set ``featurize_token`` hashes
    (declared dataclass fields, else ``__dict__``, underscore-prefixed
    lazily-attached caches excluded)."""
    if dataclasses.is_dataclass(op):
        state = {
            f.name: getattr(op, f.name, None)
            for f in dataclasses.fields(op)
        }
    else:
        state = getattr(op, "__dict__", None) or {}
    return [
        (name, value)
        for name, value in sorted(state.items())
        if not name.startswith("_") and _is_array(value)
    ]


def _iter_param_sites(fitted):
    """Yield ``(op, field, name, value)`` for every array-valued
    operator field — THE walk behind both ``named_params`` and
    ``ParamBinder``, so the two can never disagree on the namespace."""
    for i, nid in enumerate(fitted._topo):
        op = fitted.graph.operators[nid]
        for field, value in _array_fields(op):
            yield op, field, f"{i}/{type(op).__name__}/{field}", value


def named_params(fitted) -> Dict[str, Any]:
    """The fitted pipeline's parameter pytree as a flat
    ``{"<topo#>/<OpClass>/<field>": array}`` dict — the namespace
    partition rules match against. Topo position (not node id) keys
    the name so two structurally-identical pipelines built along
    different construction paths name their params identically.
    Non-array fields (nested model objects, dicts, config scalars)
    are not extracted: they stay baked into the traced program as
    constants, replicated — only what this function names can be
    sharded."""
    return {
        name: value for _, _, name, value in _iter_param_sites(fitted)
    }


def match_partition_rules(
    rules: PartitionRules,
    params: Dict[str, Any],
    *,
    unmatched: str = "error",
) -> Dict[str, PartitionSpec]:
    """Resolve each named param to the first rule whose regex
    ``re.search``-matches its name (SNIPPETS.md [2]'s
    ``match_partition_rules``, over our operator-field namespace).

    Scalars and one-element arrays are always replicated — a rule
    cannot split what has nothing to split. Params no rule matches
    raise a ``ValueError`` naming them (``unmatched="error"``, the
    default — a model silently served half-sharded is the failure
    mode this layer exists to prevent) or fall back to replicated
    under ``unmatched="replicate"``."""
    if unmatched not in ("error", "replicate"):
        raise ValueError(
            f"unmatched must be 'error' or 'replicate', got {unmatched!r}"
        )
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    specs: Dict[str, PartitionSpec] = {}
    missing: List[str] = []
    for name, value in params.items():
        arr = np.asarray(value) if not isinstance(value, jax.Array) else value
        if arr.ndim == 0 or arr.size <= 1:
            specs[name] = PartitionSpec()
            continue
        for pat, spec in compiled:
            if pat.search(name) is not None:
                specs[name] = spec
                break
        else:
            if unmatched == "replicate":
                specs[name] = PartitionSpec()
            else:
                missing.append(name)
    if missing:
        raise ValueError(
            "no partition rule matched param(s) "
            f"{missing} — add a rule, or pass unmatched='replicate' "
            "to fall back to replication explicitly"
        )
    return specs


def _validate_spec(
    name: str, shape: Tuple[int, ...], spec: PartitionSpec, mesh
) -> None:
    """Divisibility check, up front and by name: ``device_put`` under
    an uneven ``NamedSharding`` fails deep inside jax with the global
    shape — this layer owes the caller the param name and the axis."""
    entries = tuple(spec)
    if len(entries) > len(shape):
        raise ValueError(
            f"partition spec {spec} for {name} has more entries than "
            f"the param has dims ({shape})"
        )
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for axis in axes:
            if axis not in mesh.shape:
                raise ValueError(
                    f"partition spec {spec} for {name} names mesh "
                    f"axis {axis!r}, but the mesh has "
                    f"{tuple(mesh.axis_names)}"
                )
            n *= mesh.shape[axis]
        if shape[dim] % n:
            raise ValueError(
                f"param {name} dim {dim} (size {shape[dim]}) does not "
                f"divide over {n} shards of mesh axis {entry!r} — "
                "pad the model dim or change the rule"
            )


def make_shard_fns(
    specs: Dict[str, PartitionSpec], mesh=None
) -> Dict[str, Callable[[Any], jax.Array]]:
    """Per-param placement callables: each shards its param over
    ``mesh`` per the resolved spec (``device_put`` under a
    ``NamedSharding`` — the host stages each device's slice, so the
    full array never needs to fit on one device). Divisibility is
    validated per spec entry here, NOT lazily at placement time."""
    mesh = mesh or mesh_lib.current_mesh()

    def make(name: str, spec: PartitionSpec):
        def shard_fn(value: Any) -> jax.Array:
            # validate (axis names, spec length, divisibility) BEFORE
            # building the NamedSharding: jax's own errors carry the
            # global shape, ours carry the param's NAME
            _validate_spec(name, np.shape(value), spec, mesh)
            return jax.device_put(value, NamedSharding(mesh, spec))

        return shard_fn

    return {name: make(name, spec) for name, spec in specs.items()}


def make_gather_fns(
    specs: Dict[str, PartitionSpec], mesh=None
) -> Dict[str, Callable[[Any], jax.Array]]:
    """The inverse placement: each callable re-replicates its (sharded)
    param over the same mesh — checkpointing, debugging, or handing a
    served model back to host code. Gathering a model that only fits
    sharded is the caller's HBM problem; gather per-param, not all at
    once."""
    mesh = mesh or mesh_lib.current_mesh()
    replicated = NamedSharding(mesh, PartitionSpec())

    def make(name: str):
        def gather_fn(value: Any) -> jax.Array:
            return jax.device_put(value, replicated)

        return gather_fn

    return {name: make(name) for name in specs}


def params_nbytes(params: Dict[str, Any]) -> int:
    """Total parameter bytes — what a REPLICATED engine needs resident
    per device (the number the per-chip budget check compares)."""
    return sum(int(np.asarray(v).nbytes) for v in params.values())


def placed_shard_bytes(placed: Dict[str, jax.Array]) -> Dict[Any, int]:
    """Measured per-device parameter bytes of a placed (sharded) param
    tree: device -> resident bytes, summed over every param's actual
    addressable shards. The ground truth behind "this model fits the
    mesh but not one chip" — read off the buffers, not the specs."""
    per_device: Dict[Any, int] = {}
    for arr in placed.values():
        for shard in arr.addressable_shards:
            per_device[shard.device] = (
                per_device.get(shard.device, 0) + int(shard.data.nbytes)
            )
    return per_device


class ParamBinder:
    """Functionalizes a fitted pipeline's parameters: ``run(params,
    arr)`` executes the pipeline's batched apply path with the named
    param values substituted for the stored ones — under ``jax.jit``
    the params become explicit program *arguments* (sharded, placed
    once, reused every dispatch) instead of baked-in constants.

    The binder works on a PRIVATE copy of the pipeline (same graph
    topology, shallow-copied operator objects): trace-time substitution
    mutates operator fields, and the caller's fitted pipeline — shared
    by every other lane, and the thing ``featurize_token``
    fingerprints — must never observe a tracer in a field. Concurrent
    traces (two buckets warming on different threads) serialize on the
    binder lock; compiled dispatches never enter ``run`` and pay
    nothing."""

    def __init__(self, fitted):
        ops = {
            nid: copy.copy(op)
            for nid, op in fitted.graph.operators.items()
        }
        # drop the copied operators' lazily-attached caches (the
        # underscore-prefixed convention ``featurize_token`` also
        # relies on): a shallow copy of an already-used pipeline would
        # otherwise SHARE e.g. ``_vmapped_apply`` — a jit closed over
        # the ORIGINAL operator — and substitution would silently not
        # happen
        for op in ops.values():
            _scrub_caches(op)
        graph = dataclasses.replace(fitted.graph, operators=ops)
        # FittedPipeline deferred to call time would be circular-import
        # free too, but the type is needed right here
        self._pipeline = type(fitted)(graph, fitted.source, fitted.sink)
        # (operator, field, name) substitution sites + the pristine
        # values restored after every trace — the same walk that names
        # the params, so sites and namespace can't drift
        self._sites: List[Tuple[Any, str, str]] = []
        self.params: Dict[str, Any] = {}
        for op, field, name, value in _iter_param_sites(self._pipeline):
            self._sites.append((op, field, name))
            self.params[name] = value
        self._lock = threading.Lock()

    def run(self, params: Dict[str, Any], arr: Any) -> Any:
        """The traceable (params, batch) -> outputs path. Executes at
        trace time only; the restore in ``finally`` keeps tracers from
        outliving their trace inside the private pipeline's fields —
        including the lazily-attached caches the trace itself creates
        (``Transformer._jitted_vmap`` builds an inner jit over the
        operator, whose trace cache would otherwise carry this trace's
        param tracers into the next trace)."""
        with self._lock:
            try:
                for op, field, name in self._sites:
                    setattr(op, field, params[name])
                return self._pipeline._batch_run(arr)
            finally:
                for op, field, name in self._sites:
                    setattr(op, field, self.params[name])
                for op in self._pipeline.graph.operators.values():
                    _scrub_caches(op)


def resolve_param_sharding(
    param_sharding: Any,
    fitted,
    *,
    params: Optional[Dict[str, Any]] = None,
    unmatched: str = "error",
) -> Dict[str, PartitionSpec]:
    """Normalize an engine's ``param_sharding=`` argument to a resolved
    ``{name: PartitionSpec}`` tree: ``True`` means ``DEFAULT_RULES``, a
    sequence of ``(regex, PartitionSpec)`` rules is matched against the
    pipeline's named params, and a dict of already-resolved specs
    passes through (validated against the real param names). Callers
    that already extracted the named params (the engine holds its
    binder's) pass them via ``params`` to skip a second walk."""
    if params is None:
        params = named_params(fitted)
    if param_sharding is True:
        return match_partition_rules(
            DEFAULT_RULES, params, unmatched=unmatched
        )
    if isinstance(param_sharding, dict):
        unknown = sorted(set(param_sharding) - set(params))
        if unknown:
            raise ValueError(
                f"param_sharding names unknown params {unknown} "
                f"(have {sorted(params)})"
            )
        specs = {name: PartitionSpec() for name in params}
        specs.update(param_sharding)
        return specs
    return match_partition_rules(
        param_sharding, params, unmatched=unmatched
    )


__all__ = [
    "DEFAULT_RULES",
    "ParamBinder",
    "make_gather_fns",
    "make_shard_fns",
    "match_partition_rules",
    "named_params",
    "params_nbytes",
    "placed_shard_bytes",
    "resolve_param_sharding",
]
