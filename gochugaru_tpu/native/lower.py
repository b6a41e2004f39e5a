"""The bulk check lowering's pull over a batch of ``Relationship``
objects, and the grouping of their request contexts, each in one native
call (``lower.cpp``, loaded with ``ctypes.PyDLL`` so it keeps the
interpreter lock).

``pull`` returns what ``DeviceEngine._lower``'s Python pass builds from
six list comprehensions, ``NativeInterner._pack``, the type-id column and
two slot passes: the packed ids of the batch's resources then subjects,
their type ids, ``q_perm`` and ``q_srel``, bit for bit.  It does not look
the ids up: that is ``NativeInterner.lookup_packed``, under the
interner's lock.  The pull needs no interner lock: it only reads the
append-only type-name dict, under the interpreter lock.

``contexts`` returns what ``caveats.device.dedup_contexts`` makes of the
batch's non-empty ``caveat_context`` dicts, or None where that pass would
key a parameter by ``repr``, or a context is not an exact ``dict`` or
holds a key that is not an exact ``str``.
"""

from __future__ import annotations

import ctypes
import sys
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from . import lower_lib

_P64 = ctypes.POINTER(ctypes.c_int64)
_P32 = ctypes.POINTER(ctypes.c_int32)


def pull(
    rels: Sequence, interner, perm_of: Dict[str, int],
    srel_of: Dict[str, int],
) -> Optional[Tuple[bytes, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """``(buf, offsets, type_ids, q_perm, q_srel)`` of a batch: ``buf``
    the UTF-8 of the 2·B ids, resources then subjects, ``offsets``
    int64[2B + 1] their byte bounds, ``type_ids`` int32[2B] each id's
    interner type id (−1 for a type the interner does not know),
    ``q_perm`` int32[B] ``perm_of.get(resource_relation, -1)``,
    ``q_srel`` int32[B] ``srel_of.get(subject_relation, -2)``.  None
    where the library is not loaded.  Raises what the Python pass
    raises: ``AttributeError``, ``TypeError`` for a non-str id or an
    unhashable name, ``UnicodeEncodeError`` for an id with a lone
    surrogate."""
    lib = lower_lib()
    if lib is None:
        return None
    B = len(rels)
    offsets = np.empty(2 * B + 1, np.int64)
    type_ids = np.empty(2 * B, np.int32)
    q_perm = np.empty(B, np.int32)
    q_srel = np.empty(B, np.int32)
    buf = lib.gl_pull(
        rels, B, interner._types, perm_of, srel_of,
        offsets.ctypes.data_as(_P64), type_ids.ctypes.data_as(_P32),
        q_perm.ctypes.data_as(_P32), q_srel.ctypes.data_as(_P32))
    return buf, offsets, type_ids, q_perm, q_srel


def contexts(
    rels: Sequence, params: Iterable[str],
) -> Optional[Tuple[np.ndarray, List[Mapping], int]]:
    """``(index, rows, keyed)`` of a batch's request contexts: ``index``
    int32[B], −1 where ``rels[i].caveat_context`` is empty, else its
    group; ``rows`` the first context of each group, groups in the order
    of their first row; ``keyed`` the parameters some context names.
    Contexts group as ``dedup_contexts`` groups them over ``params``
    (equal values of equal types in every parameter), and the pair
    ``index[index >= 0], rows`` is its ``(index, rows)`` of the non-empty
    contexts.  None where the library is not loaded, a context is not an
    exact ``dict``, a key is not an exact ``str``, or a parameter holds a
    value other than an exact ``str`` or ``int``, a bool or None: the
    caller's Python pass groups that batch.  Raises ``AttributeError`` for a row without
    ``caveat_context``."""
    lib = lower_lib()
    if lib is None:
        return None
    B = len(rels)
    index = np.empty(B, np.int32)
    keyed = ctypes.c_int64(0)
    # interned names: a context's keys written as literals match by address
    rows = lib.gl_contexts(rels, B, tuple(map(sys.intern, params)),
                           index.ctypes.data_as(_P32), ctypes.byref(keyed))
    if rows is None:
        return None
    return index, rows, keyed.value
