"""Native runtime layer (C++ via ctypes).

The reference delegates all heavy lifting to a server; here the host-side
ingest pipeline is part of the framework, and its hot paths — bulk string
interning and the primary-order lexsort feeding the device's binary-search
layout — are implemented in C++ (``ingest.cpp``) and loaded through a C
ABI.  Everything degrades gracefully: if the shared library can't be
built/loaded (no compiler, exotic platform), ``available()`` is False and
callers fall back to the pure-numpy/python paths with identical results.

A second library, ``lower.cpp``, is the bulk check lowering's pull over a
batch of ``Relationship`` objects and the grouping of their request
contexts (``native/lower.py``): it reads Python
objects, so it is compiled against the interpreter's headers
(``sysconfig.get_paths()["include"]``) and loaded with ``ctypes.PyDLL``,
which keeps the interpreter lock through each call.  It is built and
loaded on its own: a host without ``Python.h`` loses only that pass
(``lower_lib()`` is None and ``DeviceEngine._lower`` runs its Python
pass, same columns), never the interner.

Each library is compiled on first use with g++ (the image has no pybind11;
ctypes needs only a .so), cached next to its source, and rebuilt whenever
the cached binary was not built from the current source — the source hash
is stored in a sidecar stamp file, so a stale or foreign binary is never
silently loaded (mtimes are useless for this: a fresh checkout gives
source and binary the same timestamp).  The binaries are never committed
to version control.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sysconfig
import threading
from typing import Callable, List, Optional, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


class _Library:
    """One C++ source built into one shared library: ``<dir>/<name>.cpp``
    → ``<dir>/libgochugaru_<name>.so`` plus its ``.srchash`` stamp.
    ``flag_sets`` are tried in order (extra g++ arguments); ``bind`` sets
    the loaded library's signatures."""

    def __init__(self, name: str, flag_sets: Sequence[List[str]],
                 loader: Callable[[str], ctypes.CDLL],
                 bind: Callable[[ctypes.CDLL], None], directory: str = _HERE):
        self.src = os.path.join(directory, f"{name}.cpp")
        self.so = os.path.join(directory, f"libgochugaru_{name}.so")
        self.stamp = self.so + ".srchash"
        self.flag_sets = flag_sets
        self.loader, self.bind = loader, bind
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._tried = False

    def _src_hash(self) -> Optional[str]:
        try:
            with open(self.src, "rb") as f:
                return hashlib.sha256(f.read()).hexdigest()
        except OSError:
            return None

    def _build(self, src_hash: str) -> bool:
        # build beside the target and rename: another process never loads
        # a half-written library
        tmp = f"{self.so}.{os.getpid()}.tmp"
        for flags in self.flag_sets:
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", *flags,
                   self.src, "-o", tmp]
            try:
                r = subprocess.run(cmd, capture_output=True, timeout=120)
                if r.returncode == 0:
                    os.replace(tmp, self.so)
                    with open(self.stamp, "w") as f:
                        f.write(src_hash)
                    return True
            except (OSError, subprocess.TimeoutExpired):
                return False
        return False

    def get(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None or self._tried:
                return self._lib
            self._tried = True
            try:
                want = self._src_hash()
                if want is None:
                    return None
                have = None
                if os.path.exists(self.so) and os.path.exists(self.stamp):
                    try:
                        with open(self.stamp) as f:
                            have = f.read().strip()
                    except OSError:
                        have = None
                if have != want and not self._build(want):
                    return None
                lib = self.loader(self.so)
            except OSError:
                return None
            self.bind(lib)
            self._lib = lib
            return lib

    def discard(self) -> None:
        """Remove the cached binary and forget the loaded one, so the
        next ``get`` builds afresh."""
        with self._lock:
            for path in (self.so, self.stamp):
                try:
                    os.remove(path)
                except FileNotFoundError:
                    pass
            self._lib, self._tried = None, False


def _bind_ingest(lib: ctypes.CDLL) -> None:
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


def _bind_lower(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.gl_pull.restype = c.py_object
    lib.gl_pull.argtypes = [
        c.py_object, c.c_int64, c.py_object, c.py_object, c.py_object,
        c.POINTER(c.c_int64), c.POINTER(c.c_int32),
        c.POINTER(c.c_int32), c.POINTER(c.c_int32),
    ]
    lib.gl_contexts.restype = c.py_object
    lib.gl_contexts.argtypes = [
        c.py_object, c.c_int64, c.py_object, c.POINTER(c.c_int32),
        c.POINTER(c.c_int64),
    ]


_INGEST = _Library("ingest", [["-fopenmp"], []], ctypes.CDLL, _bind_ingest)
_LOWER = _Library("lower", [["-I", sysconfig.get_paths()["include"]]],
                  ctypes.PyDLL, _bind_lower)

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
    """Discard both cached binaries and build ``ingest.cpp`` now; True
    when the fresh ingest library loaded (``lower.cpp`` builds again on
    its first use).  For entry points that must not trust a binary found
    in the tree (chip_smoke.py).  Call before anything has used the
    libraries: handles already given out keep the old mapping."""
    _LOWER.discard()
    _INGEST.discard()
    return _INGEST.get() is not None


def lib() -> Optional[ctypes.CDLL]:
    if _forced_off:
        return None
    return _INGEST.get()


def lower_lib() -> Optional[ctypes.CDLL]:
    """The lowering's pull library (``lower.cpp``), or None where it is
    switched off or failed to build."""
    if _forced_off:
        return None
    return _LOWER.get()
