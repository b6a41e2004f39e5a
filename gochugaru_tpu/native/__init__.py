"""Native runtime layer (C++ via ctypes).

The reference delegates all heavy lifting to a server; here the host-side
ingest pipeline is part of the framework, and its hot paths — bulk string
interning and the primary-order lexsort feeding the device's binary-search
layout — are implemented in C++ (``ingest.cpp``) and loaded through a C
ABI.  Everything degrades gracefully: if the shared library can't be
built/loaded (no compiler, exotic platform), ``available()`` is False and
callers fall back to the pure-numpy/python paths with identical results.

The library is compiled on first use with g++ (the image has no pybind11;
ctypes needs only a .so), cached next to this file, and rebuilt whenever
the cached binary was not built from the current ``ingest.cpp`` — the
source hash is stored in a sidecar stamp file, so a stale or foreign
binary is never silently loaded (mtimes are useless for this: a fresh
checkout gives source and binary the same timestamp).  The binary itself
is never committed to version control.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ingest.cpp")
_SO = os.path.join(_HERE, "libgochugaru_ingest.so")
_STAMP = _SO + ".srchash"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _src_hash() -> Optional[str]:
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None


def _build(src_hash: str) -> bool:
    # build beside the target and rename: another process never loads a
    # half-written library
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmds = [
        ["g++", "-O3", "-shared", "-fPIC", "-fopenmp", "-std=c++17",
         _SRC, "-o", tmp],
        # no-OpenMP fallback (serial sort)
        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
    ]
    for cmd in cmds:
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=120)
            if r.returncode == 0:
                os.replace(tmp, _SO)
                with open(_STAMP, "w") as f:
                    f.write(src_hash)
                return True
        except (OSError, subprocess.TimeoutExpired):
            return False
    return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            want = _src_hash()
            if want is None:
                return None
            have = None
            if os.path.exists(_SO) and os.path.exists(_STAMP):
                try:
                    with open(_STAMP) as f:
                        have = f.read().strip()
                except OSError:
                    have = None
            if have != want and not _build(want):
                return None
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        c = ctypes
        lib.gi_new.restype = c.c_void_p
        lib.gi_free.argtypes = [c.c_void_p]
        lib.gi_size.argtypes = [c.c_void_p]
        lib.gi_size.restype = c.c_int64
        lib.gi_intern_batch.argtypes = [
            c.c_void_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int64,
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        ]
        lib.gi_lookup_batch.argtypes = lib.gi_intern_batch.argtypes
        lib.gi_node_types.argtypes = [c.c_void_p, c.POINTER(c.c_int32), c.c_int64]
        lib.gi_key.argtypes = [
            c.c_void_p, c.c_int64, c.c_char_p, c.c_int64, c.POINTER(c.c_int32),
        ]
        lib.gi_key.restype = c.c_int64
        lib.gi_keys_batch.argtypes = [
            c.c_void_p, c.POINTER(c.c_int64), c.c_int64, c.c_char_p,
            c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int32),
        ]
        lib.gi_keys_batch.restype = c.c_int64
        for name in ("gi_lexsort4",):
            fn = getattr(lib, name)
            fn.argtypes = [
                c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                c.POINTER(c.c_int32), c.POINTER(c.c_int32),
                c.c_int64, c.POINTER(c.c_int64),
            ]
        lib.gi_lexsort2.argtypes = [
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
            c.c_int64, c.POINTER(c.c_int64),
        ]
        lib.gi_argsort1.argtypes = [
            c.POINTER(c.c_int32), c.c_int64, c.POINTER(c.c_int64),
        ]
        lib.gi_join_sorted2.argtypes = [
            c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
            c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
            c.POINTER(c.c_int64),
        ]
        lib.gi_sortperm3.argtypes = [
            c.POINTER(c.c_uint64), c.POINTER(c.c_uint64),
            c.POINTER(c.c_uint64), c.c_int64, c.POINTER(c.c_int64),
        ]
        lib.gi_hash_index32.argtypes = [
            c.POINTER(c.c_uint32), c.c_int64, c.c_int64,
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        ]
        lib.gi_hash_index32.restype = c.c_int64
        lib.gi_mix32.argtypes = [
            c.POINTER(c.c_int64), c.c_int64, c.c_int64, c.POINTER(c.c_uint32),
        ]
        lib.gi_take32.argtypes = [
            c.POINTER(c.c_int32), c.POINTER(c.c_int64), c.c_int64,
            c.POINTER(c.c_int32),
        ]
        lib.gi_take64.argtypes = [
            c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.c_int64,
            c.POINTER(c.c_int64),
        ]
        lib.gi_interleave32.argtypes = [
            c.POINTER(c.c_int64), c.c_int64, c.POINTER(c.c_int32), c.c_int64,
            c.POINTER(c.c_int32), c.c_int64,
        ]
        lib.gi_run_bounds64.argtypes = [
            c.POINTER(c.c_int64), c.c_int64, c.POINTER(c.c_int64),
        ]
        lib.gi_run_bounds64.restype = c.c_int64
        lib.gi_run_bounds32.argtypes = [
            c.POINTER(c.c_int32), c.c_int64, c.POINTER(c.c_int64),
        ]
        lib.gi_run_bounds32.restype = c.c_int64
        lib.gi_pack32.argtypes = [
            c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int64, c.c_int64,
            c.POINTER(c.c_int32),
        ]
        lib.gi_msrel1.argtypes = [
            c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.c_int64, c.c_int64,
            c.POINTER(c.c_int32),
        ]
        _lib = lib
        return _lib


#: test hook + escape hatch: GOCHUGARU_NATIVE=0 (or set_enabled(False))
#: forces every native-accelerated path onto its pure-numpy fallback —
#: tests/test_prepare_parity.py builds both ways and asserts bitwise
#: equality of every produced table.
_forced_off = os.environ.get("GOCHUGARU_NATIVE", "").strip() == "0"


def set_enabled(on: bool) -> None:
    global _forced_off
    _forced_off = not on


def enabled() -> bool:
    """Whether the native layer is currently allowed (it may still be
    unavailable if the library failed to build)."""
    return not _forced_off


def available() -> bool:
    return lib() is not None


def rebuild() -> bool:
    """Discard any cached binary and build ``ingest.cpp`` now; True when
    the fresh library loaded.  For entry points that must not trust a
    binary found in the tree (chip_smoke.py).  Call before anything has
    used the library: handles already given out keep the old mapping."""
    global _lib, _tried
    with _lock:
        for path in (_SO, _STAMP):
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
        _lib, _tried = None, False
    return _load() is not None


def lib() -> Optional[ctypes.CDLL]:
    if _forced_off:
        return None
    return _load()
