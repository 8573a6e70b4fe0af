"""ctypes loader for the native host runtime (osim_native.cpp).

Degrades gracefully: the shared library is compiled on demand with g++
(the toolchain baked into the image); if that fails, every entry point
reports unavailable and callers keep their pure-Python paths. The
library's file name carries the source's hash, so a binary built from
another version of osim_native.cpp is never loaded.
The reference's host layer is compiled Go — this is the TPU build's
equivalent compiled layer for host-side hot loops (SURVEY §2.4).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "osim_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> str:
    """libosim_native.<source sha256 prefix>.so"""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libosim_native.{digest}.so")


def _build(so: str) -> bool:
    """Compile to a private temp name, then rename: a concurrent loader
    sees either no library or a whole one."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", _SRC, "-o", tmp],
            capture_output=True,
            timeout=120,
        )
        if proc.returncode != 0:
            return False
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """The shared library, building it on first use; None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        # double-checked locking: the unlocked fast-path read above pairs
        # with these writes, but both writes happen under _lock and a stale
        # fast-path read only costs a harmless second trip into the lock
        _tried = True  # osim: audit-ok[race]
        try:
            so = _so_path()
        except OSError:
            return None
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.osim_hash_rows.argtypes = [
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS"),
        ]
        lib.osim_hash_rows.restype = None
        lib.osim_parse_quantity_one.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.osim_parse_quantity_one.restype = ctypes.c_int
        # publish under _lock; the unlocked reader sees either None (and
        # takes the lock) or the fully-initialized CDLL
        _lib = lib  # osim: audit-ok[race]
        return _lib


def available() -> bool:
    return load() is not None


def parse_quantity_one(s: str) -> Optional[Tuple[int, int, int, int]]:
    """Scalar fast path: (milli_ceil, milli_floor, base_ceil, base_floor), or
    None when unavailable / the value needs the exact Python path."""
    lib = load()
    if lib is None:
        return None
    b = s.encode()
    mc = ctypes.c_int64()
    mf = ctypes.c_int64()
    bc = ctypes.c_int64()
    bf = ctypes.c_int64()
    if not lib.osim_parse_quantity_one(
        b, len(b),
        ctypes.byref(mc), ctypes.byref(mf), ctypes.byref(bc), ctypes.byref(bf),
    ):
        return None
    return mc.value, mf.value, bc.value, bf.value


def hash_rows(data: np.ndarray) -> Optional[np.ndarray]:
    """128-bit hash per row of a 2-D uint8 array -> uint64[n, 2], or None
    when the native library is unavailable."""
    lib = load()
    if lib is None:
        return None
    data = np.ascontiguousarray(data, np.uint8)
    n, row_bytes = data.shape
    out = np.zeros((n, 2), np.uint64)
    lib.osim_hash_rows(data, n, row_bytes, out)
    return out
