"""Native runtime components — build-on-demand C++ via ctypes.

The reference had no native code of its own (SURVEY.md §2b.4), but its
performance-critical runtime lived in its dependencies' native layers. This
package is the rebuild's native runtime layer: small C++ cores compiled once
per machine with the system ``g++`` (no pybind11 in this image — plain C ABI
+ ctypes) and cached next to the source. Everything degrades gracefully: if
no compiler is present, callers get ``None`` from :func:`load_dkps` and fall
back to the pure-Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "dkps.cpp")
_BUILD_DIR = os.environ.get(
    "DISTKERAS_NATIVE_BUILD_DIR", os.path.join(_HERE, "_build")
)

_lock = threading.Lock()
_cached: ctypes.CDLL | None = None
_failed: str | None = None


def _so_path() -> str:
    """The built library's path, keyed by a hash of the SOURCE'S CONTENTS:
    a copy of the tree can reorder mtimes, and a library built from another
    tree's source must never load in place of this one's."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libdkps-{digest}.so")


def _build(so: str) -> str | None:
    """Compile dkps.cpp → ``so`` unless that file is already there; return
    error or None."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    if os.path.exists(so):
        return None
    tmp = so + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", tmp, _SRC,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ unavailable: {e}"
    if proc.returncode != 0:
        return f"g++ failed: {proc.stderr[-2000:]}"
    os.replace(tmp, so)  # atomic: concurrent builders race benignly
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.dkps_server_create.restype = ctypes.c_void_p
    lib.dkps_server_create.argtypes = [
        f32p, ctypes.c_uint64, ctypes.c_int, ctypes.c_double,
        ctypes.c_char_p, ctypes.c_int, ctypes.c_double, ctypes.c_double,
    ]
    lib.dkps_server_port.restype = ctypes.c_int
    lib.dkps_server_port.argtypes = [ctypes.c_void_p]
    lib.dkps_server_start.restype = ctypes.c_int
    lib.dkps_server_start.argtypes = [ctypes.c_void_p]
    lib.dkps_server_stop.restype = None
    lib.dkps_server_stop.argtypes = [ctypes.c_void_p]
    lib.dkps_server_crash.restype = None
    lib.dkps_server_crash.argtypes = [ctypes.c_void_p]
    lib.dkps_server_wal_open.restype = ctypes.c_int
    lib.dkps_server_wal_open.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_double,
    ]
    lib.dkps_server_set_ema.restype = ctypes.c_int
    lib.dkps_server_set_ema.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_server_restore_worker.restype = None
    lib.dkps_server_restore_worker.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64,
    ]
    lib.dkps_server_destroy.restype = None
    lib.dkps_server_destroy.argtypes = [ctypes.c_void_p]
    lib.dkps_server_num_updates.restype = ctypes.c_uint64
    lib.dkps_server_num_updates.argtypes = [ctypes.c_void_p]
    lib.dkps_server_set_num_updates.restype = None
    lib.dkps_server_set_num_updates.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.dkps_server_get_center.restype = None
    lib.dkps_server_get_center.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_server_set_center.restype = None
    lib.dkps_server_set_center.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_server_get_ema.restype = ctypes.c_int
    lib.dkps_server_get_ema.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_server_record_pull.restype = None
    lib.dkps_server_record_pull.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.dkps_server_stats.restype = None
    lib.dkps_server_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.dkps_client_connect.restype = ctypes.c_void_p
    lib.dkps_client_connect.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_uint32, ctypes.c_uint64,
    ]
    lib.dkps_client_from_fd.restype = ctypes.c_void_p
    lib.dkps_client_from_fd.argtypes = [
        ctypes.c_int, ctypes.c_uint32, ctypes.c_uint64,
    ]
    # shm ring lane (ISSUE 12): the segment is mapped by Python
    # (multiprocessing.shared_memory) and both endpoints attach by base
    # pointer — see dkps.cpp "Shared-memory ring lane"
    lib.dkps_server_attach_shm.restype = ctypes.c_int
    lib.dkps_server_attach_shm.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
    ]
    lib.dkps_client_connect_shm.restype = ctypes.c_void_p
    lib.dkps_client_connect_shm.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint64,
    ]
    lib.dkps_client_set_timeout_ms.restype = ctypes.c_int
    lib.dkps_client_set_timeout_ms.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dkps_client_pull.restype = ctypes.c_int64
    lib.dkps_client_pull.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_client_pull_int8.restype = ctypes.c_int64
    lib.dkps_client_pull_int8.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_client_commit.restype = ctypes.c_int
    lib.dkps_client_commit.argtypes = [ctypes.c_void_p, f32p]
    lib.dkps_client_commit_int8.restype = ctypes.c_int
    lib.dkps_client_commit_int8.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int8),
        ctypes.POINTER(ctypes.c_uint64), f32p, ctypes.c_uint32,
    ]
    lib.dkps_client_commit_seq.restype = ctypes.c_int
    lib.dkps_client_commit_seq.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, f32p,
    ]
    lib.dkps_client_commit_seq_e.restype = ctypes.c_int
    lib.dkps_client_commit_seq_e.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, f32p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.dkps_client_fence.restype = ctypes.c_int64
    lib.dkps_client_fence.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.dkps_client_exchange.restype = ctypes.c_int64
    lib.dkps_client_exchange.argtypes = [
        ctypes.c_void_p, ctypes.c_uint8, ctypes.c_uint64, ctypes.c_uint64,
        f32p, f32p, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.dkps_server_set_shard.restype = None
    lib.dkps_server_set_shard.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
    ]
    lib.dkps_client_shard_info.restype = ctypes.c_int
    lib.dkps_client_shard_info.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.dkps_server_fence.restype = ctypes.c_uint64
    lib.dkps_server_fence.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.dkps_server_fence_epoch.restype = ctypes.c_uint64
    lib.dkps_server_fence_epoch.argtypes = [ctypes.c_void_p]
    lib.dkps_client_heartbeat.restype = ctypes.c_int
    lib.dkps_client_heartbeat.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.dkps_client_deregister.restype = ctypes.c_int
    lib.dkps_client_deregister.argtypes = [ctypes.c_void_p]
    lib.dkps_server_set_pool_size.restype = None
    lib.dkps_server_set_pool_size.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.dkps_server_set_trace.restype = None
    lib.dkps_server_set_trace.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.dkps_client_trace_scrape.restype = ctypes.c_int64
    lib.dkps_client_trace_scrape.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
    ]
    lib.dkps_client_join.restype = ctypes.c_int
    lib.dkps_client_join.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.dkps_client_drain.restype = ctypes.c_int
    lib.dkps_client_drain.argtypes = [ctypes.c_void_p, ctypes.c_uint8]
    lib.dkps_client_close.restype = None
    lib.dkps_client_close.argtypes = [ctypes.c_void_p]
    return lib


def load_dkps(required: bool = False) -> ctypes.CDLL | None:
    """Load (building if needed) the dkps shared library.

    Returns ``None`` when the library cannot be built and ``required`` is
    False; raises ``RuntimeError`` with the compiler output otherwise.
    """
    global _cached, _failed
    with _lock:
        if _cached is not None:
            return _cached
        so = _so_path()
        if _failed is None:
            _failed = _build(so) or ""
        if _failed:
            if required:
                raise RuntimeError(f"cannot build libdkps: {_failed}")
            return None
        _cached = _bind(ctypes.CDLL(so))
        return _cached
