"""Native (C++) components, loaded via ctypes with on-demand compilation.

The reference obtains native speed from C dependencies (msgpack, lz4,
crick, ucx — SURVEY §2); this package holds our own equivalents.  The
shared library builds once per machine into the package directory with
``g++ -O3 -shared`` and every consumer has a pure-python fallback, so a
missing toolchain degrades gracefully.  ``DTPU_NATIVE_DISABLE=1``
forces the pure-python fallbacks everywhere (the no-toolchain path,
testable on a box that has g++).

Rebuild keying: the library is stale when the content hash of any
source, the source list or the flags differ from what the ``.buildinfo``
sidecar recorded at build time.  Content, not mtimes: a copied tree
(the chip tool copies the working tree, untracked ``.so`` included) can
carry a library newer than sources it was not built from.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import logging
import os
import subprocess
import threading
from typing import Callable

logger = logging.getLogger("distributed_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "_dtpu_native.so")
_BUILDINFO_PATH = _LIB_PATH + ".buildinfo"
_SOURCES = [
    os.path.join(_HERE, "tdigest.cpp"),
    os.path.join(_HERE, "graphpack.cpp"),
    os.path.join(_HERE, "engine.cpp"),
]
# NO -ffast-math and no reassociation flags, ever: engine.cpp promises
# bit-identical IEEE rounding with CPython's float ops
_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def disabled() -> bool:
    """True when the DTPU_NATIVE_DISABLE env kill-switch is set: every
    consumer silently uses its pure-python fallback."""
    return os.environ.get("DTPU_NATIVE_DISABLE", "") not in ("", "0")


def _build_spec() -> dict:
    """The identity of the build: what the ``.buildinfo`` sidecar
    records and what staleness is keyed on — the flags and, per source
    (by basename, so a relocated checkout does not rebuild), the sha256
    of its content."""
    sources = {}
    for src in _SOURCES:
        with open(src, "rb") as f:
            sources[os.path.basename(src)] = hashlib.sha256(
                f.read()
            ).hexdigest()
    return {"flags": list(_FLAGS), "sources": sources}


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_BUILDINFO_PATH) as f:
            recorded = json.load(f)
    except (OSError, ValueError):
        return True
    return recorded != _build_spec()


def _build() -> bool:
    # the spec is taken before compiling: a source edited mid-build
    # leaves a sidecar that no longer matches, so the next load rebuilds
    spec = _build_spec()
    cmd = ["g++", *_FLAGS, *_SOURCES, "-o", _LIB_PATH]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build failed to run: %s", e)
        return False
    if proc.returncode != 0:
        logger.warning(
            "native build failed:\n%s", proc.stderr.decode()[-2000:]
        )
        return False
    try:
        with open(_BUILDINFO_PATH, "w") as f:
            json.dump(spec, f)
    except OSError as e:  # stale-able but functional
        logger.warning("could not record native buildinfo: %s", e)
    return True


def prebuild_async(on_ready: Callable[[], None] | None = None) -> None:
    """Kick off the g++ build on a daemon thread (servers call this at
    start so the first native consumer on the event loop never blocks
    on a compile).  ``on_ready`` fires IN THE BUILD THREAD when the
    library is loaded — callers on an event loop must trampoline with
    ``call_soon_threadsafe`` (the scheduler server uses this to attach
    the native transition engine once the build lands)."""

    def run() -> None:
        if load() is not None and on_ready is not None:
            try:
                on_ready()
            except Exception:
                logger.exception("native prebuild on_ready callback failed")

    threading.Thread(target=run, name="dtpu-native-build", daemon=True).start()


def load_nowait() -> ctypes.CDLL | None:
    """The library if already built/loaded; never compiles (safe on the
    event loop)."""
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed or disabled() or _needs_build():
            return None
    return load()


def load() -> ctypes.CDLL | None:
    """The native library, building it on first use; None if unavailable."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed or disabled():
            return None
        if _needs_build() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            logger.warning("cannot load native library: %s", e)
            _build_failed = True
            return None
        # signatures
        lib.tdigest_new.restype = ctypes.c_void_p
        lib.tdigest_new.argtypes = [ctypes.c_double]
        lib.tdigest_free.argtypes = [ctypes.c_void_p]
        lib.tdigest_add.argtypes = [
            ctypes.c_void_p, ctypes.c_double, ctypes.c_double
        ]
        lib.tdigest_add_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_int64
        ]
        lib.tdigest_quantile.restype = ctypes.c_double
        lib.tdigest_quantile.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.tdigest_count.restype = ctypes.c_double
        lib.tdigest_count.argtypes = [ctypes.c_void_p]
        lib.tdigest_min.restype = ctypes.c_double
        lib.tdigest_min.argtypes = [ctypes.c_void_p]
        lib.tdigest_max.restype = ctypes.c_double
        lib.tdigest_max.argtypes = [ctypes.c_void_p]
        lib.tdigest_serialize.restype = ctypes.c_int64
        lib.tdigest_serialize.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_int64
        ]
        lib.tdigest_merge_serialized.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_int64
        ]
        _i32p = ctypes.POINTER(ctypes.c_int32)
        _f32p = ctypes.POINTER(ctypes.c_float)
        lib.graphpack_full.restype = ctypes.c_int64
        lib.graphpack_full.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            _f32p, _f32p, _i32p, _i32p,
            ctypes.c_double, ctypes.c_double,
            _i32p, _i32p, _i32p,
            _f32p, _i32p, _i32p, _f32p, _f32p, _f32p,
        ]
        lib.graphpack_topo.restype = ctypes.c_int64
        lib.graphpack_topo.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            _f32p, _i32p, _i32p,
            _i32p, _i32p, _i32p,
            _i32p, _i32p, _f32p, _i32p, _i32p,
        ]
        lib.graphpack_fill.restype = None
        lib.graphpack_fill.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            _f32p, _f32p, _i32p, _i32p,
            _i32p, _i32p, _f32p, _i32p,
            ctypes.c_double, ctypes.c_double,
            _f32p, _i32p, _i32p, _f32p, _f32p, _f32p,
        ]
        lib.unpack_assignment.restype = None
        lib.unpack_assignment.argtypes = [
            ctypes.c_int64, _i32p, _i32p, _i32p,
            ctypes.POINTER(ctypes.c_int8),
        ]
        # ---- engine.cpp (scheduler/native_engine.py bridge)
        _i64p = ctypes.POINTER(ctypes.c_int64)
        _f64p = ctypes.POINTER(ctypes.c_double)
        _u8p = ctypes.POINTER(ctypes.c_uint8)
        _vp = ctypes.c_void_p
        lib.eng_new.restype = _vp
        lib.eng_new.argtypes = []
        lib.eng_free.argtypes = [_vp]
        lib.eng_params.argtypes = [
            _vp, ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_double, ctypes.c_double, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ]
        lib.eng_worker_upsert.argtypes = [
            _vp, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int64, ctypes.c_double, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_char_p,
        ]
        lib.eng_worker_close.argtypes = [_vp, ctypes.c_int32]
        lib.eng_prefix_set.argtypes = [_vp, ctypes.c_int32, ctypes.c_double]
        lib.eng_prefix_get.restype = ctypes.c_double
        lib.eng_prefix_get.argtypes = [_vp, ctypes.c_int32]
        lib.eng_group_upsert.argtypes = [
            _vp, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32, _i32p
        ]
        lib.eng_task_sync_bulk.argtypes = [
            _vp, ctypes.c_int64, _i32p, _u8p, _u8p, _i32p, _i32p,
            _i64p, _i32p, _i32p, _f64p,
            _i64p, _i32p, _u8p,
            _i64p, _i32p,
            _i64p, _i32p,
            _i64p, _i32p,
        ]
        lib.eng_task_forget.argtypes = [_vp, ctypes.c_int32]
        lib.eng_set_tape.argtypes = [
            _vp, _i32p, _i32p, _i32p, _i32p, _f64p, _f64p, ctypes.c_int64
        ]
        lib.eng_drain_finished.restype = ctypes.c_int32
        lib.eng_drain_finished.argtypes = [
            _vp, ctypes.c_int64, _i32p, _i32p, _i64p, _f64p, _u8p, _i64p
        ]
        lib.eng_drain_recs.restype = ctypes.c_int32
        lib.eng_drain_recs.argtypes = [_vp, ctypes.c_int64, _i32p, _i32p]
        lib.eng_tape_len.restype = ctypes.c_int64
        lib.eng_tape_len.argtypes = [_vp]
        lib.eng_escape_row.restype = ctypes.c_int32
        lib.eng_escape_row.argtypes = [_vp]
        lib.eng_escape_target.restype = ctypes.c_int32
        lib.eng_escape_target.argtypes = [_vp]
        lib.eng_escape_why.restype = ctypes.c_int32
        lib.eng_escape_why.argtypes = [_vp]
        lib.eng_pending_recs.restype = ctypes.c_int64
        lib.eng_pending_recs.argtypes = [_vp, _i32p, _i32p, ctypes.c_int64]
        lib.eng_touched.restype = ctypes.c_int64
        lib.eng_touched.argtypes = [_vp, _i32p, _f64p, ctypes.c_int64]
        lib.eng_total_occupancy.restype = ctypes.c_double
        lib.eng_total_occupancy.argtypes = [_vp]
        lib.eng_transitions.restype = ctypes.c_int64
        lib.eng_transitions.argtypes = [_vp]
        lib.eng_escapes.restype = ctypes.c_int64
        lib.eng_escapes.argtypes = [_vp]
        lib.eng_escape_count.restype = ctypes.c_int64
        lib.eng_escape_count.argtypes = [_vp, ctypes.c_int32]
        lib.eng_counts.restype = None
        lib.eng_counts.argtypes = [_vp, _i64p]
        lib.eng_replica_add.argtypes = [_vp, ctypes.c_int32, ctypes.c_int32]
        lib.eng_replica_remove.argtypes = [
            _vp, ctypes.c_int32, ctypes.c_int32
        ]
        lib.eng_task_nbytes.argtypes = [
            _vp, ctypes.c_int32, ctypes.c_int64
        ]
        lib.eng_task_who_wants.argtypes = [
            _vp, ctypes.c_int32, ctypes.c_int32
        ]
        lib.eng_task_read.argtypes = [_vp, ctypes.c_int32, _i64p]
        lib.eng_worker_read.argtypes = [_vp, ctypes.c_int32, _f64p, _i64p]
        _lib = lib
        return _lib
