"""AOT executable cache: zero-cold-start serving.

Every number since the first serving PR has been a *warm* number: a
fresh gateway process still pays trace + lowering + XLA compile per
bucket before ``/readyz`` flips, which is exactly the cold-start tax
that caps how fast the stack can scale out or roll a new engine
generation. The persistent XLA compilation cache (PR 1) removes the
*compile* but a restarted process still pays trace + lowering +
cache-replay per bucket program.

This module removes the whole thing. ``CompiledPipeline.warmup``
already AOT-lowers every bucket program for the device cost models
(``lower().compile()``); an ``AotStore`` serializes those compiled
executables once — ``jax.experimental.serialize_executable`` — into an
on-disk store keyed by a **fingerprint** of everything that could make
a stored program wrong to reuse:

- the per-example input spec (leaf shapes + dtypes) and the engine's
  full bucket list + the specific bucket,
- the donation + sharding configuration (donation is baked into the
  executable as input/output aliasing),
- jax + jaxlib versions, the backend ("cpu"/"tpu"/"gpu"), the device
  kind ("TPU v4", ...) and device count (serialized programs are
  PJRT-executable bytes — they do not survive a toolchain or hardware
  change),
- a **model token**: a content digest of the fitted pipeline's
  operators and their parameter arrays. The weights are *constants
  inside the serialized program*, so two models with identical shapes
  MUST NOT share an entry — a collision would silently serve another
  model's predictions.

On the load side ``warmup`` installs a deserialized executable
*before any trace happens* for that bucket: a replica (or the
autoscaler's next-generation engine) goes from ``exec()`` to serving
in roughly deserialize time. The contract is **absent-not-broken**,
the same as the device-observability plane: any miss, fingerprint
mismatch, corrupt entry, or deserialize failure falls back silently
to the normal jit + persistent-compile-cache path and is *counted*,
never raised, on the serving path:

- ``keystone_aot_cache_hits_total`` / ``_misses_total`` /
  ``_errors_total`` counters,
- ``keystone_aot_cache_load_seconds`` histogram (deserialize + install
  wall time per entry),
- an ``aot_cache`` block in the admin endpoint's ``/varz`` ``build``
  document (store dir, entry count, hit/miss/error totals).

The store directory is configured beside the persistent compile cache
(``parallel.runtime.setup_aot_cache``: argument, then
``$KEYSTONE_AOT_CACHE``, then ``~/.cache/keystone_tpu/aot``); the
``serve-aot-build`` CLI app pre-populates it at build/deploy time so a
brand-new host starts hot (``bin/smoke-aot.sh`` drills exactly that;
what it buys on the chip is unmeasured: ROADMAP D3).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

# bump to invalidate every existing store entry on a format change
STORE_FORMAT = "keystone-aot-v1"

ENTRY_SUFFIX = ".aotx"

# deserialize+install is milliseconds; a pathological NFS store is
# seconds — the histogram must resolve both
LOAD_SECONDS_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0,
)


# -- version/identity probes (module-level so tests can fake a jax
# -- upgrade without touching the real modules) ---------------------------

def runtime_versions() -> Dict[str, str]:
    """The toolchain part of the fingerprint: serialized executables
    are PJRT bytes and do not survive a jax/jaxlib upgrade."""
    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
    }


def device_identity() -> Dict[str, Any]:
    """The hardware part of the fingerprint. Best-effort: a backend
    that fails to report identity yields stable placeholders (the
    store then keys only on backend name — still safe, since
    deserialization itself rejects foreign executables)."""
    import jax

    ident: Dict[str, Any] = {
        "backend": None, "device_kind": None, "device_count": None,
    }
    try:
        ident["backend"] = jax.default_backend()
        devices = jax.devices()
        if devices:
            ident["device_kind"] = devices[0].device_kind
            ident["device_count"] = len(devices)
    except Exception:
        pass
    return ident


def _hash_update(h, value: Any) -> None:
    """Deterministically fold one operator attribute into the model
    token. Arrays hash by shape/dtype/bytes (the weights ARE the
    program constants); containers recurse; primitives hash by repr;
    anything else contributes its type name only — weaker, but the
    parameter arrays carry the real identity.

    Every component is FRAMED (type tag + terminator): unframed
    concatenation made distinct parameter sets collide — e.g.
    ``(1, 23)`` and ``(12, 3)`` both fold to the bytes ``123`` — and a
    token collision here means one model silently serving another
    model's predictions."""
    import jax

    if isinstance(value, (np.ndarray, np.generic, jax.Array)):
        arr = np.asarray(value)
        h.update(
            b"a<" + str(arr.shape).encode() + b"|"
            + str(arr.dtype).encode() + b"|"
        )
        h.update(arr.tobytes())
        h.update(b">")
    elif isinstance(value, (str, bytes, int, float, bool, type(None))):
        h.update(b"p<" + repr(value).encode() + b">")
    elif isinstance(value, dict):
        h.update(b"d<")
        for k in sorted(value, key=repr):
            h.update(b"k<" + repr(k).encode() + b">")
            _hash_update(h, value[k])
        h.update(b">")
    elif isinstance(value, (list, tuple)):
        h.update(b"l<")
        for v in value:
            _hash_update(h, v)
        h.update(b">")
    elif dataclasses.is_dataclass(value) and not isinstance(value, type):
        # a node held by another (RowwiseRun's nodes, a Convolver's
        # whitener): its fields are parameters too
        h.update(b"o<" + type(value).__qualname__.encode() + b"|")
        for f in dataclasses.fields(value):
            if not f.name.startswith("_"):
                h.update(b"f<" + f.name.encode() + b">")
                _hash_update(h, getattr(value, f.name, None))
        h.update(b">")
    else:
        h.update(b"t<" + type(value).__qualname__.encode() + b">")


def pipeline_token(fitted) -> str:
    """Content digest of a ``FittedPipeline``: operator classes in
    topological order plus every operator's attribute values (parameter
    arrays hashed by content). Two fitted pipelines with identical
    architectures but different weights get different tokens — the
    property that keeps one model's cached executable from ever
    serving another model's predictions.

    Memoized on the pipeline object (the same lazily-attached-cache
    idiom its operators use): an N-lane gateway builds N engines per
    generation from ONE fitted pipeline, and hashing a large model's
    every parameter N times per cold start would be repeated work on
    exactly the path this module optimizes. A ``FittedPipeline`` is
    immutable once fit (refits build new objects), so the cache can't
    go stale."""
    cached = getattr(fitted, "_aot_pipeline_token", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for nid in fitted._topo:
        op = fitted.graph.operators[nid]
        # the WIRING is part of the model: same operators in the same
        # topo order compute different things when the edges differ
        # (a Join fed (A(x), x) vs (A(x), A(x))). Ids are hashed by
        # repr — graphs built along different construction paths may
        # token-differ for the same model (a harmless miss), but two
        # different computations can never token-collide.
        h.update(
            b"n<" + repr(nid).encode() + b"|"
            + ",".join(
                repr(d) for d in fitted.graph.dependencies[nid]
            ).encode()
            + b">"
        )
        h.update(b"op<" + type(op).__qualname__.encode() + b">")
        if dataclasses.is_dataclass(op):
            # declared fields only: transformers are dataclasses whose
            # fields ARE the parameters
            state = {
                f.name: getattr(op, f.name, None)
                for f in dataclasses.fields(op)
            }
        else:
            state = getattr(op, "__dict__", None) or {}
        for name in sorted(state):
            if name.startswith("_"):
                # lazily-attached caches (_vmapped_apply,
                # _arr_digest_cache, ...) appear after first use; a
                # token that shifted when the pipeline RAN would turn
                # every restart into a miss
                continue
            h.update(b"f<" + name.encode() + b">")
            _hash_update(h, state[name])
    h.update(
        b"s<"
        + repr(fitted.graph.sink_dependencies[fitted.sink]).encode()
        + b">"
    )
    token = h.hexdigest()
    try:
        fitted._aot_pipeline_token = token
    except Exception:
        pass  # slots/frozen pipeline: just recompute next time
    return token


def runtime_identity() -> Dict[str, Any]:
    """``runtime_versions() + device_identity()`` in one dict — the
    warmup-invariant part of the fingerprint, computed once per warmup
    and passed to every ``bucket_key`` call (re-probing jax per bucket
    would be repeated work on exactly the cold path this module
    optimizes)."""
    return {**runtime_versions(), **device_identity()}


def bucket_key(
    specs: Sequence[Tuple[Tuple[int, ...], Any]],
    buckets: Sequence[int],
    bucket: int,
    donate: bool,
    shard: bool,
    model_token: str,
    identity: Optional[Dict[str, Any]] = None,
    featurize_token: Optional[str] = None,
    sharding_token: Optional[str] = None,
    namespace: Optional[str] = None,
) -> Tuple[str, Dict[str, Any]]:
    """Fingerprint one bucket program. Returns ``(key, meta)`` where
    ``key`` is the store filename stem and ``meta`` is the full
    human-readable field dict — stored inside the entry and re-checked
    on load, so even a filename collision cannot install a wrong
    executable. ``identity`` is ``runtime_identity()``, passed in by
    loops that fingerprint many buckets. ``featurize_token`` is the
    ``pipeline_token`` of a fused device-side featurize stage (engine
    ``featurize=``), or None for plain model programs: the featurize
    parameters are constants inside the serialized executable just like
    the model weights, so fused and unfused programs — and programs
    fused with DIFFERENT featurizers — must never share an entry.
    ``sharding_token`` is ``serving/sharding.sharding_token``'s digest
    of a model-sharded engine's resolved partition-spec tree + mesh
    topology, or None for replicated programs: a mesh-sharded
    executable is a structurally different program (GSPMD-partitioned,
    params as arguments) and must never share an entry with a
    replicated one — while replicated programs' fingerprints stay
    byte-identical to pre-sharding stores (no fleet-wide cold start on
    upgrade). ``namespace`` is the model-zoo partition
    (``AotStore(namespace=model_id)``): two co-hosted models never
    share a cache slot even if their content tokens somehow agreed,
    and the GC accounts each model's bytes separately."""
    meta: Dict[str, Any] = {
        "format": STORE_FORMAT,
        "specs": [
            [list(shape), str(np.dtype(dtype))] for shape, dtype in specs
        ],
        "buckets": [int(b) for b in buckets],
        "bucket": int(bucket),
        "donate": bool(donate),
        "shard": bool(shard),
        "model_token": model_token,
        # present ONLY for fused programs: unconditionally stamping
        # None here would shift every unfused key and cold-start every
        # existing store on upgrade. Fused vs unfused still can never
        # collide — the extra key changes the fused hash, and the meta
        # re-check rejects a planted entry whose key set differs.
        **(
            {"featurize_token": featurize_token}
            if featurize_token is not None else {}
        ),
        # same stamped-only-when-set discipline as featurize_token:
        # unconditionally writing None here would shift every
        # replicated key and cold-start every existing store
        **(
            {"sharding_token": sharding_token}
            if sharding_token is not None else {}
        ),
        # ditto: single-model processes (namespace None) keep their
        # pre-zoo fingerprints byte-identical
        **(
            {"namespace": namespace}
            if namespace is not None else {}
        ),
        **(identity if identity is not None else runtime_identity()),
    }
    blob = json.dumps(meta, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest(), meta


# entry file layout: magic, 8-byte big-endian meta length, the meta as
# canonical JSON, then the pickled executable payload. The JSON
# preamble is validated against the requested fingerprint BEFORE the
# pickle bytes are touched.
ENTRY_MAGIC = b"KAOT1\n"


class AotStore:
    """On-disk store of serialized bucket executables.

    ``save``/``load`` never raise on the serving path: every failure is
    counted (``errors``) and reported as "no entry" so the caller falls
    back to the normal compile path. Entries are written atomically
    (tmp file + rename), so a crashed writer can never leave a
    half-entry a reader would trip over.

    TRUST BOUNDARY: the store dir. Entries carry pickled PJRT
    executables (``jax.experimental.serialize_executable`` is
    pickle-based), and unpickling executes code — so loading an entry
    extends write-access-to-the-dir into code-execution-in-the-server,
    exactly like loading a model checkpoint. The dir is created 0700,
    the fingerprint meta rides in a plain-JSON preamble that is
    validated BEFORE any pickle bytes are touched (a mismatched or
    malformed entry is rejected unpickled), and the remaining rule is
    operational: only let build steps you trust as much as the serving
    binary write to the store."""

    # an in-flight save's tmp file older than this is a crashed
    # writer's leftover, safe to sweep (a live save lasts seconds)
    STALE_TMP_S = 3600.0

    def __init__(
        self, root: str, registry=None, namespace: Optional[str] = None
    ):
        self.root = os.path.abspath(root)
        # the model-zoo partition: folded into every bucket_key this
        # store's engines compute (engine warmup reads it off the
        # store), so entries from different namespaces coexist in one
        # root dir but can never be loaded across — the meta re-check
        # rejects a planted foreign entry before unpickling. None is
        # the single-model default and keeps pre-zoo keys stable.
        self.namespace = namespace
        os.makedirs(self.root, mode=0o700, exist_ok=True)
        self._sweep_stale_tmp()
        # plain per-store totals for status()/tests, plus the shared
        # scrape families on the (global) MetricsRegistry
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.errors = 0  # guarded-by: _lock
        self.saves = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        from keystone_tpu.observability.registry import (
            get_global_registry,
        )

        reg = registry if registry is not None else get_global_registry()
        self._hits_c = reg.counter(
            "keystone_aot_cache_hits_total",
            "AOT executable store: bucket programs installed from a "
            "serialized entry (no trace, no compile)",
        )
        self._misses_c = reg.counter(
            "keystone_aot_cache_misses_total",
            "AOT executable store: lookups that found no entry "
            "(fell back to the normal compile path)",
        )
        self._errors_c = reg.counter(
            "keystone_aot_cache_errors_total",
            "AOT executable store: corrupt/mismatched/undeserializable "
            "entries and failed saves (fell back silently)",
        )
        self._load_h = reg.histogram(
            "keystone_aot_cache_load_seconds",
            "wall seconds to deserialize, validate, and install one "
            "stored bucket executable (hits only)",
            buckets=LOAD_SECONDS_BUCKETS,
        )
        self._bytes_g = reg.gauge(
            "keystone_aot_store_bytes",
            "on-disk bytes of AOT store entries, per model-zoo "
            "namespace ('default' for single-model stores)",
            ("namespace",),
        )
        self._publish_bytes()

    # -- store layout ------------------------------------------------------

    def path_for(self, key: str) -> str:
        return os.path.join(self.root, key + ENTRY_SUFFIX)

    def entries(self) -> list:
        try:
            return sorted(
                f[: -len(ENTRY_SUFFIX)]
                for f in os.listdir(self.root)
                # mkstemp tmp names also end in the suffix; a crashed
                # writer's leftover must not count as an entry
                if f.endswith(ENTRY_SUFFIX) and not f.startswith(".")
            )
        except OSError:
            return []

    def _sweep_stale_tmp(self) -> None:
        """Remove crashed writers' ``.tmp-*`` leftovers (age-gated: a
        CONCURRENT process's in-flight save must survive)."""
        try:
            now = time.time()
            for f in os.listdir(self.root):
                if not f.startswith(".tmp-"):
                    continue
                path = os.path.join(self.root, f)
                try:
                    if now - os.path.getmtime(path) > self.STALE_TMP_S:
                        os.unlink(path)
                except OSError:
                    pass
        except OSError:
            pass

    # -- accounting --------------------------------------------------------

    def _count(self, which: str) -> None:
        with self._lock:
            setattr(self, which, getattr(self, which) + 1)
        counter = {
            "hits": self._hits_c,
            "misses": self._misses_c,
            "errors": self._errors_c,
        }.get(which)
        if counter is not None:
            counter.inc()

    def record_error(self) -> None:
        """An entry that loaded but failed to EXECUTE (the engine
        validates with one dispatch before trusting it) — or a
        pipeline that couldn't be fingerprinted at all — is charged
        here by the caller."""
        self._count("errors")

    def record_hit(self, seconds: Optional[float] = None) -> None:
        """One stored executable VALIDATED and installed. Counted by
        the engine after its validation dispatch succeeds — not by
        ``load()`` — so ``keystone_aot_cache_hits_total`` never counts
        an executable that deserialized but was thrown away, and the
        load-seconds histogram (``seconds``: the full deserialize +
        validate + install wall) never shows healthy latencies for
        installs that didn't happen."""
        self._count("hits")
        if seconds is not None:
            self._load_h.observe(seconds)

    # -- save / load -------------------------------------------------------

    def save(self, key: str, compiled, meta: Dict[str, Any]) -> Optional[str]:
        """Serialize one ``jax.stages.Compiled`` under ``key``.
        Best-effort: backends whose executables don't serialize (or a
        read-only store dir) log + count an error and return None —
        serving proceeds, the store just stays cold."""
        from jax.experimental import serialize_executable

        path = self.path_for(key)
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            meta_blob = json.dumps(meta, sort_keys=True).encode()
            blob = (
                ENTRY_MAGIC
                + len(meta_blob).to_bytes(8, "big")
                + meta_blob
                + pickle.dumps(
                    {
                        "payload": payload,
                        "in_tree": in_tree,
                        "out_tree": out_tree,
                    },
                    protocol=pickle.HIGHEST_PROTOCOL,
                )
            )
            fd, tmp = tempfile.mkstemp(
                dir=self.root, prefix=".tmp-", suffix=ENTRY_SUFFIX
            )
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)  # atomic: readers never see a
                # partial entry
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception:
            self._count("errors")
            logger.info(
                "aot store: could not serialize bucket executable to "
                "%s", path, exc_info=True,
            )
            return None
        with self._lock:
            self.saves += 1
        self._publish_bytes()
        logger.info(
            "aot store: saved bucket %s executable (%d bytes) to %s",
            meta.get("bucket"), len(blob), path,
        )
        return path

    def load(self, key: str, meta: Dict[str, Any]) -> Tuple[Any, str]:
        """Deserialize the entry under ``key`` into a callable
        ``jax.stages.Compiled``. Returns ``(loaded, "hit")`` on
        success, ``(None, "miss")`` when the entry is absent, and
        ``(None, "error")`` when it exists but is corrupt or its
        stored meta disagrees with ``meta`` — the outcome rides back
        so the engine's per-bucket report tells the same story the
        hit/miss/error counters do. The hit COUNTER is not bumped
        here: the caller confirms with ``record_hit()`` once the
        executable survives its validation dispatch. Never raises."""
        from jax.experimental import serialize_executable

        path = self.path_for(key)
        if not os.path.exists(path):
            self._count("misses")
            return None, "miss"
        try:
            with open(path, "rb") as f:
                data = f.read()
            stored_meta, body = self._split_entry(data)
            if stored_meta != meta:
                # key collision or a fingerprint-field drift: the
                # stored program is not provably THIS program — and
                # nothing of it has been unpickled
                raise ValueError(
                    "stored meta disagrees with the requested "
                    "fingerprint"
                )
            blob = pickle.loads(body)
            loaded = serialize_executable.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"]
            )
        except Exception:
            self._count("errors")
            logger.info(
                "aot store: entry %s unusable; falling back to "
                "compile", path, exc_info=True,
            )
            return None, "error"
        return loaded, "hit"

    @staticmethod
    def _split_entry(data: bytes) -> Tuple[Dict[str, Any], bytes]:
        """Entry bytes -> (meta dict from the JSON preamble, pickled
        payload bytes). Raises on anything malformed — WITHOUT having
        unpickled a single byte."""
        if not data.startswith(ENTRY_MAGIC):
            raise ValueError("not an AOT store entry (bad magic)")
        off = len(ENTRY_MAGIC)
        n = int.from_bytes(data[off:off + 8], "big")
        meta_end = off + 8 + n
        if n <= 0 or meta_end > len(data):
            raise ValueError("truncated AOT store entry")
        return json.loads(data[off + 8:meta_end]), data[meta_end:]

    def read_meta(self, key: str) -> Optional[Dict[str, Any]]:
        """The stored fingerprint meta of one entry (JSON preamble
        only — nothing is unpickled), or None when absent/corrupt.
        Ops tooling and tests can audit a store without trusting it."""
        try:
            with open(self.path_for(key), "rb") as f:
                return self._split_entry(f.read())[0]
        except Exception:
            return None

    # -- namespace accounting + GC -----------------------------------------

    def _owned_entries(self) -> list:
        """``(key, size_bytes, mtime)`` for every entry in THIS store's
        namespace, mtime-ascending (the LRU eviction order). Entries
        whose JSON preamble is unreadable are claimed by every
        namespace: they can never be loaded, so any GC may clear them.
        Meta is read from the preamble only — auditing a store must
        never unpickle it."""
        owned = []
        for key in self.entries():
            meta = self.read_meta(key)
            if meta is not None and meta.get("namespace") != self.namespace:
                continue
            try:
                st = os.stat(self.path_for(key))
            except OSError:
                continue  # raced a concurrent eviction
            owned.append((key, int(st.st_size), st.st_mtime))
        owned.sort(key=lambda e: (e[2], e[0]))
        return owned

    def namespace_bytes(self) -> int:
        """On-disk bytes of this namespace's entries — what the
        ``keystone_aot_store_bytes{namespace}`` gauge exports."""
        return sum(size for _, size, _ in self._owned_entries())

    def _publish_bytes(self) -> None:
        try:
            self._bytes_g.set(
                float(self.namespace_bytes()),
                (self.namespace or "default",),
            )
        except Exception:
            # the gauge is observability, not correctness: a raced
            # listdir/stat must never fail a save or a gc
            logger.debug("aot store: bytes gauge update failed",
                         exc_info=True)

    def gc(
        self, max_bytes: int, pinned: Sequence[str] = ()
    ) -> Dict[str, Any]:
        """Evict least-recently-used entries (mtime order — ``save``
        rewrites touch it, so recently refreshed generations survive)
        until this NAMESPACE's on-disk bytes fit ``max_bytes``. Entries
        whose key is in ``pinned`` are never evicted, even if that
        leaves the namespace over budget (a pinned hot model's programs
        beat the byte target). Other namespaces' entries are invisible:
        one model's churn can never GC another model's executables.
        Best-effort like every store op — an unlinkable entry is
        counted as an error and skipped, never raised."""
        report: Dict[str, Any] = {
            "namespace": self.namespace, "evicted": [],
            "evicted_bytes": 0,
        }
        pinned_set = set(pinned)
        owned = self._owned_entries()
        total = sum(size for _, size, _ in owned)
        for key, size, _ in owned:
            if total <= max_bytes:
                break
            if key in pinned_set:
                continue
            try:
                os.unlink(self.path_for(key))
            except OSError:
                self._count("errors")
                continue
            total -= size
            report["evicted"].append(key)
            report["evicted_bytes"] += size
        report["kept_bytes"] = total
        report["over_budget"] = total > max_bytes
        self._publish_bytes()
        if report["evicted"]:
            logger.info(
                "aot store gc (namespace %s): evicted %d entries "
                "(%d bytes), %d bytes kept",
                self.namespace or "default", len(report["evicted"]),
                report["evicted_bytes"], report["kept_bytes"],
            )
        return report

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "dir": self.root,
                "namespace": self.namespace,
                "entries": len(self.entries()),
                "hits": self.hits,
                "misses": self.misses,
                "errors": self.errors,
                "saves": self.saves,
            }


# -- the process-configured store (parallel.runtime owns the dir) ---------

_configured: Optional[AotStore] = None
_configured_lock = threading.Lock()


def configured_store() -> Optional[AotStore]:
    """The store at the dir ``parallel.runtime.setup_aot_cache``
    configured for this process, or None when none was configured
    (engines then skip the AOT path entirely — the default for
    library/test use; the serving CLIs call setup unless
    ``--no-cache``)."""
    global _configured
    from keystone_tpu.parallel import runtime

    root = runtime.aot_cache_dir()
    if root is None:
        return None
    with _configured_lock:
        if _configured is None or _configured.root != os.path.abspath(root):
            try:
                _configured = AotStore(root)
            except Exception:
                # the dir was creatable at setup time but isn't now
                # (cache purge, NFS outage): the serving path must get
                # "no store", never an exception — same contract as
                # every other store failure
                logger.info(
                    "aot store at %s unavailable; serving without it",
                    root, exc_info=True,
                )
                return None
        return _configured


def namespaced_store(namespace: str) -> Optional[AotStore]:
    """A model-zoo view over the process-configured store dir: same
    root, entries fingerprinted (and GC'd) under ``namespace``. None
    when no store dir is configured — the zoo then serves without AOT,
    exactly like a single-model engine would. Not memoized: each model
    owns its view (per-namespace byte gauges and GC state are
    per-instance)."""
    from keystone_tpu.parallel import runtime

    root = runtime.aot_cache_dir()
    if root is None:
        return None
    try:
        return AotStore(root, namespace=str(namespace))
    except Exception:
        logger.info(
            "aot store at %s unavailable for namespace %s; serving "
            "without it", root, namespace, exc_info=True,
        )
        return None


def status() -> Dict[str, Any]:
    """The ``aot_cache`` block of ``/varz``'s build document."""
    store = configured_store()
    if store is None:
        return {"dir": None}
    return store.status()


# -- serve-aot-build: pre-populate the store at build/deploy time ---------

def build_main(argv=None) -> int:
    """``python -m keystone_tpu serve-aot-build [--buckets 8,32,128]``
    — compile every bucket of the (serve-gateway demo) pipeline once and serialize the executables into the AOT store, so
    a brand-new host's ``serve-gateway`` goes from exec() to serving
    without a single XLA compile. Real deployments call
    ``CompiledPipeline.warmup`` over their own fitted pipeline with
    the store configured — this entry is the demo/smoke/bench path."""
    import argparse

    import jax.numpy as jnp

    from keystone_tpu.parallel.runtime import (
        setup_aot_cache,
        setup_compilation_cache,
    )
    from keystone_tpu.serving.demo_model import build_pipeline

    ap = argparse.ArgumentParser(
        prog="keystone_tpu serve-aot-build",
        description="pre-populate the AOT serialized-executable store",
    )
    ap.add_argument("--buckets", default="8,32,128",
                    help="comma-separated row buckets (must match the "
                    "serving config that will load the store)")
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--aot-cache", default=None, metavar="DIR",
                    help="store dir (default: $KEYSTONE_AOT_CACHE, "
                    "then ~/.cache/keystone_tpu/aot)")
    args = ap.parse_args(argv)

    # the persistent compile cache makes the build's own
    # lower().compile() replay from disk on a rebuild, and warmup's jit
    # dispatch replay the same program instead of compiling twice
    setup_compilation_cache()
    root = setup_aot_cache(args.aot_cache)
    if root is None:
        print(json.dumps({"error": "aot cache dir unavailable"}))
        return 1
    store = configured_store()
    if store is None:
        # the dir existed at setup time but the store can't open it
        # now (permission flip, NFS blip): same clean error path as an
        # uncreatable dir, not an AttributeError
        print(json.dumps({"error": "aot store unavailable", "dir": root}))
        return 1
    buckets = tuple(int(b) for b in args.buckets.split(","))
    fitted = build_pipeline(d=args.d, hidden=args.hidden, depth=args.depth)
    engine = fitted.compiled(
        buckets=buckets, name="aot-build", aot_store=store
    )
    t0 = time.perf_counter()
    times = engine.warmup(
        example=jnp.zeros((args.d,), jnp.float32)
    )
    report = {
        "dir": root,
        "buckets": list(engine.buckets),
        "warmup_seconds": {
            str(b): round(t, 3) for b, t in times.items()
        },
        "wall_seconds": round(time.perf_counter() - t0, 3),
        "aot": engine.aot_report(),
        **store.status(),
    }
    print(json.dumps(report), flush=True)
    # entries must exist for every bucket at exit: freshly saved, hit
    # from a previous build, or REPAIRED (a broken entry recompiled
    # and re-saved reports status "error" + fallback "saved" — the
    # store is whole, and failing the deploy step over an already
    # fixed entry would just make the rerun mysteriously green)
    ok = all(
        v.get("status") in ("saved", "hit")
        or v.get("fallback") == "saved"
        for v in (
            engine.aot_report().get(b, {}) for b in engine.buckets
        )
    )
    return 0 if ok else 1
