"""Set arithmetic the plain references share: relations as sorted int64
pair keys, adjacency as CSR, frontiers as (probe, node) pairs.

Nothing here imports the program.  A world's ``reference`` is a few
lines over these helpers, vectorised over all probes at once.
"""

from __future__ import annotations

import numpy as np


def pair_keys(a, b) -> np.ndarray:
    """Sorted, distinct keys of the (a, b) pairs."""
    return np.unique(np.asarray(a, np.int64) << 32 | np.asarray(b, np.int64))


def unique_pairs(a, b):
    """The distinct (a, b) pairs, sorted (an import refuses duplicates)."""
    key = pair_keys(a, b)
    return key >> 32, key & 0xFFFFFFFF


def has_pair(keys: np.ndarray, a, b) -> np.ndarray:
    """For each (a[i], b[i]): is the pair in ``keys`` (from pair_keys)?"""
    want = np.asarray(a, np.int64) << 32 | np.asarray(b, np.int64)
    at = np.searchsorted(keys, want)
    at[at == keys.shape[0]] = 0
    return keys[at] == want if keys.shape[0] else np.zeros(want.shape, bool)


class CSR:
    """src → its dst list, for ``n`` sources."""

    def __init__(self, src, dst, n: int) -> None:
        order = np.argsort(src, kind="stable")
        self.dst = np.asarray(dst, np.int64)[order]
        self.start = np.searchsorted(np.asarray(src)[order], np.arange(n + 1))

    def expand(self, rows, nodes):
        """Frontier step: every (row, node) becomes (row, d) for each d
        in node's list."""
        lo, hi = self.start[nodes], self.start[nodes + 1]
        n = hi - lo
        total = int(n.sum())
        # position of each output inside its node's list
        within = np.arange(total) - np.repeat(np.cumsum(n) - n, n)
        return np.repeat(rows, n), self.dst[np.repeat(lo, n) + within]


def any_by_row(rows, hit, n: int) -> np.ndarray:
    """OR of ``hit`` per row index."""
    out = np.zeros(n, bool)
    out[rows[hit]] = True
    return out


def member_closure(direct_keys: np.ndarray, parent, child, n: int) -> np.ndarray:
    """Keys of (group, user) with user in group directly or through
    nested groups; ``parent[i]`` contains every member of ``child[i]``."""
    keys = direct_keys
    nest = CSR(child, parent, n)  # child → the groups that contain it
    while True:
        g, u = keys >> 32, keys & 0xFFFFFFFF
        users, up = nest.expand(u, g)  # rows carry the user
        grown = np.union1d(keys, up << 32 | users)
        if grown.shape[0] == keys.shape[0]:
            return keys
        keys = grown
