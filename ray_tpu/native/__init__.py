"""Native (C++) runtime components, loaded via ctypes.

The compute plane is JAX/XLA/Pallas; this package holds the native pieces
of the *runtime* around it — currently the per-node shared-memory object
store (reference: `src/ray/object_manager/plasma/`, `store.cc`).

The shared library is built on demand with g++ (no pybind11 in the image;
plain C ABI + ctypes keeps the binding dependency-free) and cached next to
the source under a name that carries a hash of the source, so a copied
tree cannot load a library built from other code; callers fall back to
the pure-Python implementation when the toolchain is unavailable
(`native_store_lib() is None`).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "store.cc")
_LIB_PREFIX = os.path.join(_DIR, "libray_tpu_store.")

_lock = threading.Lock()
_lib = None
_build_failed = False


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"{_LIB_PREFIX}{digest}.so"


def _build(lib_path: str) -> bool:
    tmp = f"{lib_path}.{os.getpid()}.tmp"   # processes may build at once
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-o",
           tmp, _SRC, "-lrt", "-pthread"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired) as exc:
        logger.warning("native store build failed to launch: %s", exc)
        return False
    if proc.returncode != 0:
        logger.warning("native store build failed:\n%s", proc.stderr[-2000:])
        return False
    os.replace(tmp, lib_path)
    for stale in glob.glob(_LIB_PREFIX + "*so"):
        if stale != lib_path:
            try:
                os.remove(stale)
            except OSError:
                pass   # another process got there first
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u64 = ctypes.c_uint64
    p = ctypes.c_void_p
    s = ctypes.c_char_p
    lib.rts_open.argtypes = [s, s, u64]
    lib.rts_open.restype = p
    lib.rts_close.argtypes = [p]
    lib.rts_create.argtypes = [p, s, u64, ctypes.c_char_p, ctypes.c_int]
    lib.rts_create.restype = ctypes.c_int
    lib.rts_seal.argtypes = [p, s]
    lib.rts_seal.restype = ctypes.c_int
    lib.rts_contains.argtypes = [p, s]
    lib.rts_contains.restype = ctypes.c_int
    lib.rts_info.argtypes = [p, s, ctypes.c_char_p, ctypes.c_int,
                             ctypes.POINTER(u64)]
    lib.rts_info.restype = ctypes.c_int
    lib.rts_read.argtypes = [p, s, u64, u64, ctypes.c_char_p]
    lib.rts_read.restype = ctypes.c_int64
    lib.rts_write.argtypes = [p, s, u64, ctypes.c_char_p, u64]
    lib.rts_write.restype = ctypes.c_int
    lib.rts_delete.argtypes = [p, s]
    lib.rts_delete.restype = ctypes.c_int
    lib.rts_pin.argtypes = [p, s, s]
    lib.rts_unpin.argtypes = [p, s, s]
    lib.rts_unpin_worker.argtypes = [p, s]
    lib.rts_size.argtypes = [p, s]
    lib.rts_size.restype = ctypes.c_int64
    lib.rts_used.argtypes = [p]
    lib.rts_used.restype = u64
    lib.rts_stats.argtypes = [p, u64 * 5]
    lib.rts_inventory.argtypes = [p, ctypes.c_char_p, ctypes.c_int]
    lib.rts_inventory.restype = ctypes.c_int
    lib.rts_shutdown.argtypes = [p]
    return lib


def native_store_lib():
    """The bound CDLL for the native store, building it if needed; None if
    the toolchain is missing or the build failed (callers use the Python
    store)."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            _build_failed = True
            return None
        try:
            _lib = _bind(ctypes.CDLL(lib_path))
        except OSError as exc:
            logger.warning("native store load failed: %s", exc)
            _build_failed = True
            return None
        return _lib
