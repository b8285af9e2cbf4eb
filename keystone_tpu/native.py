"""ctypes bindings for the native IO runtime (native/io.cc).

The reference reaches native code via JNI (utils/external/VLFeat.scala,
EncEval.scala); here the native layer serves the host input pipeline —
multi-threaded CSV parsing and CIFAR record decoding — since the compute
kernels are XLA programs. The shared libraries are not tracked: the
first use builds them from ``native/*.cc`` (``make -C native``). When the
build fails the numpy/PIL implementations take over, and the failure is
logged as a WARNING with make's stderr and kept for ``status()`` — never
swallowed.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libkeystone_io.so")
_JPEG_LIB_PATH = os.path.join(_NATIVE_DIR, "libkeystone_jpeg.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False
_jpeg_lib: Optional[ctypes.CDLL] = None
_jpeg_tried = False
_build_error: Optional[str] = None  # the last failed make, for status()
# first use commonly happens from inside the streaming loader's decode
# THREAD pool — without the lock, threads arriving while another is
# mid-load see tried=True/lib=None and silently take the slow fallback
# for the whole stream. RLock: the jpeg loader calls _load() while
# holding it (one shared build attempt covers both libraries).
_load_lock = threading.RLock()


def _load() -> Optional[ctypes.CDLL]:
    # the unlocked fast path must only trust _tried AFTER a load attempt
    # fully completed — _load_locked flips it as its last action, never
    # before, or waiting threads would see tried=True/lib=None mid-load
    # and silently take the slow fallback for the whole stream
    if _lib is not None or _tried:
        return _lib
    with _load_lock:
        if _lib is not None or _tried:
            return _lib
        try:
            return _load_locked()
        finally:
            globals()["_tried"] = True


def _is_stale() -> bool:
    return os.path.exists(_LIB_PATH) and any(
        os.path.getmtime(os.path.join(_NATIVE_DIR, f))
        > os.path.getmtime(_LIB_PATH)
        for f in os.listdir(_NATIVE_DIR)
        if f.endswith(".cc") or f == "Makefile"
    )


def _build_once() -> None:
    """Run make under an exclusive file lock: spawn-based decode WORKERS
    all reach first-load together, and concurrent linkers writing the
    same .so would hand some process a partially-written library (it
    would then silently use the slow fallback for its whole lifetime).
    The in-process _load_lock cannot serialize across processes."""
    import fcntl

    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        # another process may have built while we waited on the lock
        if os.path.exists(_LIB_PATH) and not _is_stale():
            return
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )


def _load_locked() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    if (not os.path.exists(_LIB_PATH) or _is_stale()) and os.path.exists(
        os.path.join(_NATIVE_DIR, "Makefile")
    ):
        try:
            _build_once()
        except (OSError, subprocess.SubprocessError) as e:
            stderr = getattr(e, "stderr", None) or b""
            if isinstance(stderr, bytes):
                stderr = stderr.decode(errors="replace")
            _build_error = f"{e}: {stderr.strip()[-800:]}"
            logger.warning(
                "native build failed; the numpy/PIL implementations "
                "take over: %s", _build_error,
            )
            if not os.path.exists(_LIB_PATH):
                return None
            # rebuild failed but a previously built library exists: load
            # it — missing newer symbols are guarded per-function
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.csv_dims.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.csv_dims.restype = ctypes.c_int
    lib.csv_read_f32.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
    ]
    lib.csv_read_f32.restype = ctypes.c_int
    lib.cifar_read.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.cifar_read.restype = ctypes.c_int64
    if not hasattr(lib, "text_ngram_hash_tf"):
        _lib = lib  # stale build without text.cc: IO still usable
        return _lib
    lib.text_ngram_hash_tf.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int,
    ]
    lib.text_ngram_hash_tf.restype = ctypes.c_int64
    _lib = lib
    return _lib


def _load_jpeg() -> Optional[ctypes.CDLL]:
    """The JPEG decoder lives in its own shared library (it links the
    system libjpeg; native/Makefile builds it best-effort so the IO lib
    survives environments without libjpeg)."""
    global _jpeg_lib
    if _jpeg_lib is not None or _jpeg_tried:
        return _jpeg_lib
    with _load_lock:
        if _jpeg_lib is not None or _jpeg_tried:
            return _jpeg_lib
        try:
            return _load_jpeg_locked()
        finally:
            globals()["_jpeg_tried"] = True


def _load_jpeg_locked() -> Optional[ctypes.CDLL]:
    global _jpeg_lib
    _load()  # one shared build attempt covers both libraries
    if not os.path.exists(_JPEG_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_JPEG_LIB_PATH)
    except OSError:
        return None
    lib.jpeg_decode_f32.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.jpeg_decode_f32.restype = ctypes.c_int
    lib.jpeg_decode_batch_f32.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int,
    ]
    lib.jpeg_decode_batch_f32.restype = ctypes.c_int64
    _jpeg_lib = lib
    return _jpeg_lib


def native_available() -> bool:
    return _load() is not None


def status() -> Dict[str, Optional[str]]:
    """Which implementation each native entry point resolved to (this
    triggers the build on first use) and the build failure, if there
    was one — for start-up logs; ``chip_smoke.py`` prints it."""
    return {
        "io": "native" if _load() is not None else "numpy",
        "jpeg": "native" if _load_jpeg() is not None else "PIL",
        "build_error": _build_error,
    }


def jpeg_native_available() -> bool:
    return _load_jpeg() is not None


def jpeg_decode_f32(data: bytes, target: int) -> Optional[np.ndarray]:
    """Decode one JPEG to a (target, target, 3) float32 RGB array via the
    native fast path (native/jpeg.cc: DCT-scaled draft decode + triangle
    resize, GIL released for the whole call). Returns None when the
    library is unavailable or this image needs the PIL fallback (corrupt
    stream, CMYK)."""
    lib = _load_jpeg()
    if lib is None:
        return None
    out = np.empty((target, target, 3), np.float32)
    rc = lib.jpeg_decode_f32(
        data, len(data), target,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out if rc == 0 else None


def jpeg_decode_batch_f32(
    blobs, target: int, num_threads: int = 0
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Decode a list of JPEG byte strings in one native call with an
    internal thread pool. Returns ``(images (n, target, target, 3)
    float32, ok (n,) bool)``; failed slots have undefined pixels and
    ok=False. Returns None when the library is unavailable."""
    lib = _load_jpeg()
    if lib is None:
        return None
    n = len(blobs)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    concat = b"".join(blobs)
    out = np.empty((n, target, target, 3), np.float32)
    ok = np.zeros(n, np.uint8)
    lib.jpeg_decode_batch_f32(
        concat,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        target,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads,
    )
    return out, ok.astype(bool)


def read_csv_f32(
    path: str, delimiter: str = ",", num_threads: int = 0
) -> np.ndarray:
    """Numeric CSV -> (rows, cols) float32. Native multi-threaded parser
    when available, np.loadtxt otherwise."""
    lib = _load()
    if lib is None or delimiter not in (",", " ", "\t"):
        return np.loadtxt(
            path, delimiter=delimiter, dtype=np.float32, ndmin=2
        )
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    if lib.csv_dims(path.encode(), ctypes.byref(rows), ctypes.byref(cols)):
        raise OSError(f"cannot read {path}")
    out = np.empty((rows.value, cols.value), np.float32)
    rc = lib.csv_read_f32(
        path.encode(),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        rows.value,
        cols.value,
        num_threads,
    )
    if rc != 0:
        # ragged or malformed — let numpy produce the proper error
        return np.loadtxt(
            path, delimiter=delimiter, dtype=np.float32, ndmin=2
        )
    return out


def text_ngram_hash_tf(
    docs,
    min_order: int,
    max_order: int,
    num_features: int,
    binarize: bool = False,
    num_threads: int = 0,
):
    """Fused trim/lowercase/tokenize/rolling-ngram-hash TF over a list of
    ASCII document strings (native/text.cc). Returns ``(row_ptr int64
    (n+1,), cols int32 (nnz,), vals float32 (nnz,))`` with per-document
    columns ascending — hash-identical to composing Trim -> LowerCase ->
    Tokenizer -> NGramsHashingTF. Returns None (caller falls back to the
    Python nodes) when the library is unavailable or any doc is
    non-ASCII (C++ tokenization is byte-level)."""
    if num_features <= 0:  # C-side modulo-by-zero would SIGFPE
        raise ValueError(f"num_features must be positive: {num_features}")
    lib = _load()
    if lib is None or not hasattr(lib, "text_ngram_hash_tf"):
        return None
    try:
        blobs = [d.encode("ascii") for d in docs]
    except UnicodeEncodeError:
        return None
    n = len(blobs)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    concat = b"".join(blobs)
    row_ptr = np.zeros(n + 1, np.int64)
    cap = max(2 * len(concat) + 16, 1024)
    for _ in range(2):
        cols = np.empty(cap, np.int32)
        vals = np.empty(cap, np.float32)
        nnz = lib.text_ngram_hash_tf(
            concat,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n, min_order, max_order, num_features, int(binarize),
            row_ptr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            cap,
            num_threads or (os.cpu_count() or 1),
        )
        if nnz >= 0:
            return row_ptr, cols[:nnz], vals[:nnz]
        cap = int(row_ptr[n])  # exact requirement, filled before -1
    return None


def read_cifar(
    path: str, channels: int = 3, dim: int = 32
) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR binary -> (labels int32 (n,), images float32 (n, dim, dim, c))."""
    lib = _load()
    rec_len = 1 + channels * dim * dim
    size = os.path.getsize(path)
    n = size // rec_len
    if lib is None:
        raw = np.fromfile(path, dtype=np.uint8)[: n * rec_len].reshape(
            n, rec_len
        )
        labels = raw[:, 0].astype(np.int32)
        images = (
            raw[:, 1:]
            .reshape(n, channels, dim, dim)
            .transpose(0, 2, 3, 1)
            .astype(np.float32)
        )
        return labels, images
    labels = np.empty(n, np.int32)
    images = np.empty((n, dim, dim, channels), np.float32)
    got = lib.cifar_read(
        path.encode(),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        channels,
        dim,
    )
    if got < 0:
        raise OSError(f"cannot read {path}")
    return labels[:got], images[:got]
