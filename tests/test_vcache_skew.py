"""The verdict cache inside ONE revision, under skewed keys: what a
read-only deployment (YCSB C over an RBAC graph, chipbench's
``rbac10m_zipf``) makes of it.  The serving handle under threads of
zipfian-repeated requests against engine/oracle.py — under budget, with a
budget of a few hundred entries so that generations rotate all through,
and through ``submit_columns`` —, a write between two reads, and the
eviction itself: an entry that is read survives, the counts stay exact,
and the work is the entries dropped, not the entries held."""

import threading

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_latency_mode,
    with_verdict_cache,
)
from gochugaru_tpu.engine import vcache
from gochugaru_tpu.engine.oracle import SnapshotOracle, T
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background

CTX = background()
REL_B = vcache.VerdictCache.REL_ENTRY_BYTES
COL_B = vcache.VerdictCache.COL_ENTRY_BYTES
REPOS, USERS, TEAMS, ORGS = 400, 80, 8, 4

SCHEMA = """
definition user {}
definition team { relation member: user }
definition org {
    relation admin: user
    relation member: user | team#member
}
definition repo {
    relation org: org
    relation maintainer: user | team#member
    relation reader: user
    permission admin = org->admin + maintainer
    permission read = reader + admin + org->member
}
"""


def _world(*opts):
    """A small GitHub-shaped world (chipbench/worlds/rbac.py's schema)
    behind a latency-mode client."""
    c = new_tpu_evaluator(with_latency_mode(), *opts)
    c.write_schema(CTX, SCHEMA)
    rng = np.random.default_rng(27)
    txn = rel.Txn()
    touch = lambda r, l, s: txn.touch(rel.must_from_triple(r, l, s))
    for t in range(TEAMS):
        for u in rng.choice(USERS, 6, replace=False):
            touch(f"team:t{t}", "member", f"user:u{u}")
    for o in range(ORGS):
        touch(f"org:o{o}", "admin", f"user:u{o}")
        touch(f"org:o{o}", "member", f"team:t{o}#member")
        touch(f"org:o{o}", "member", f"user:u{o + 10}")
    for r in range(REPOS):
        touch(f"repo:r{r}", "org", f"org:o{r % ORGS}")
        touch(f"repo:r{r}", "maintainer", f"team:t{rng.integers(TEAMS)}#member")
        touch(f"repo:r{r}", "reader", f"user:u{rng.integers(USERS)}")
    return c, c.write(CTX, txn)


def _oracle(c):
    o = SnapshotOracle(c.store.snapshot_for(consistency.full()))
    return lambda rels: [o.check_relationship(r) == T for r in rels]


def _zipfian_requests(seed, records=3000, requests=96, per=8):
    """``requests`` requests of ``per`` checks over ``records`` (repo,
    user) pairs, ranks drawn with p(i) ~ 1/i: the hot pairs repeat
    within and across requests."""
    rng = np.random.default_rng(seed)
    pairs = np.stack([rng.integers(0, REPOS, records),
                      rng.integers(0, USERS, records)], 1)
    cdf = np.cumsum(1.0 / np.arange(1, records + 1))
    at = np.searchsorted(cdf, rng.random(requests * per) * cdf[-1])
    return [
        [rel.must_from_triple(f"repo:r{r}", "read", f"user:u{u}")
         for r, u in pairs[at[i * per:(i + 1) * per]]]
        for i in range(requests)
    ]


def _columns(c, rels):
    snap = c.store.snapshot_for(consistency.full())
    look = snap.interner.lookup
    return (
        np.array([look("repo", r.resource_id) for r in rels], np.int32),
        np.full(len(rels), snap.compiled.slot_of_name["read"], np.int32),
        np.array([look("user", r.subject_id) for r in rels], np.int32),
    )


# ---------------------------------------------------------------------------
# the served path, 8 threads of zipfian-repeated requests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry,budget,rotates", [
    ("check", True, False),  # (a) the default 64 MB: never over budget
    ("check", REL_B * 300, True),  # (b) generations rotate all through
    ("submit_columns", COL_B * 300, True),  # (c) the columnar mirror
])
def test_served_zipfian_requests_equal_the_oracle(entry, budget, rotates):
    c, _ = _world(with_verdict_cache(budget))
    m = metrics.default
    pool = _zipfian_requests(seed=5)
    want = [_oracle(c)(qs) for qs in pool]
    cols = [_columns(c, qs) for qs in pool] if entry != "check" else None
    h0, e0 = m.counter("cache.hits"), m.counter("cache.evicted_entries")
    wrong = []
    with c.with_serving(cs=consistency.min_latency()) as h:
        def caller(w):
            ctx = CTX.with_timeout(120.0)
            for i in range(w, len(pool), 8):
                if entry == "check":
                    got = h.check(ctx, *pool[i], client_id=w)
                else:
                    got = h.submit_columns(
                        ctx, *cols[i], client_id=w).result(timeout=120.0)
                if [bool(v) for v in got] != want[i]:
                    wrong.append(i)

        threads = [threading.Thread(target=caller, args=(w,))
                   for w in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # a second pass over the same requests: every hot pair is held
        again = [i for i in range(0, len(pool), 7)
                 if [bool(v) for v in h.check(CTX, *pool[i])] != want[i]]
    assert not wrong and not again
    assert m.counter("cache.hits") > h0
    st = c._vcache.stats()
    assert st["bytes"] <= st["max_bytes"]
    evicted = m.counter("cache.evicted_entries") - e0
    assert (evicted > 0 and st["rotations"] >= 2) if rotates else (
        evicted == 0 and st["rotations"] == 0)


def test_a_read_at_least_a_write_answers_from_the_new_revision():
    """(d) A cached verdict belongs to its revision: after a write, the
    read at ``at_least(token)`` is evaluated at the new revision, and the
    old revision's entry still serves a reader pinned to it."""
    c, rev = _world(with_verdict_cache())
    m = metrics.default
    q = rel.must_from_triple("repo:r7", "read", "user:u79")
    edge = rel.must_from_triple("repo:r7", "reader", "user:u79")
    with c.with_serving(cs=consistency.min_latency()) as h:
        before = h.check(CTX, q)
        assert before == _oracle(c)([q])
        h0 = m.counter("cache.hits")
        assert h.check(CTX, q) == before  # now a cached verdict
        assert m.counter("cache.hits") == h0 + 1
    txn = rel.Txn()
    (txn.delete if before[0] else txn.touch)(edge)
    token = c.write(CTX, txn)
    with c.with_serving(cs=consistency.at_least(token)) as h:
        after = h.check(CTX, q)
    assert after == [not before[0]] == _oracle(c)([q])
    assert c.check(CTX, consistency.snapshot(rev), q) == before
    assert len(c._vcache.resident_revisions) == 2


# ---------------------------------------------------------------------------
# eviction inside one revision
# ---------------------------------------------------------------------------


class _Kind:
    """The two kinds of entry behind one face, for the structural tests."""

    def __init__(self, kind, cap):
        self.kind, self.m = kind, metrics.Metrics()
        self.entry_bytes = REL_B if kind == "rel" else COL_B
        self.vc = vcache.VerdictCache(
            max_bytes=self.entry_bytes * cap, registry=self.m)

    def keys(self, lo, hi):
        if self.kind == "rel":
            return [((f"repo:r{i}", "read", "user:u"), vcache.EMPTY_CTX_FP)
                    for i in range(lo, hi)]
        return np.arange(lo, hi, dtype=np.int64)

    def insert(self, lo, hi):
        ks = self.keys(lo, hi)
        if self.kind == "rel":
            self.vc.insert_rels(1, [(k, True) for k in ks], now_us=9)
        else:
            self.vc.insert_cols(1, ks, np.ones(hi - lo, bool), now_us=9)

    def hit(self, i) -> bool:
        ks = self.keys(i, i + 1)
        if self.kind == "rel":
            return self.vc.lookup_rels(1, ks)[0] == (True, 9)
        return int(self.vc.lookup_cols(1, ks)[0]) == (9 << 1) | 1

    def held(self) -> int:
        sh = self.vc._revs[1]
        return sum(len(sh[k]) for k in ("c", "c_old", "r", "r_old"))


@pytest.mark.parametrize("kind", ["rel", "col"])
def test_an_entry_that_is_read_outlives_the_entries_that_are_not(kind):
    """(e) Entry 0 is read between the inserts and is still held after
    twenty budgets' worth of them; its neighbours, never read again, are
    gone; bytes stay under the budget and the gauges equal what the
    generations hold."""
    k = _Kind(kind, cap=400)
    k.insert(0, 50)
    for lo in range(50, 8050, 50):
        k.insert(lo, lo + 50)
        assert k.hit(0)
        st = k.vc.stats()
        assert st["bytes"] <= st["max_bytes"]
        assert st["entries"] == k.held()
        assert st["bytes"] == st["entries"] * k.entry_bytes
    assert not any(k.hit(i) for i in range(1, 50))
    assert k.hit(8049)  # the newest is there
    assert k.m.gauge("cache.entries") == k.held()
    assert k.m.gauge("cache.bytes") == k.vc.stats()["bytes"]
    assert k.m.counter("cache.evicted_entries") >= 8050 - 400 - 50
    assert k.m.counter("cache.evicted_revisions") == 0


@pytest.mark.parametrize("kind", ["rel", "col"])
def test_eviction_work_is_what_it_drops_not_what_is_held(kind):
    """(f) A generation goes whole: the cache retires one for every half
    budget of new entries, however many inserts that takes and however
    many entries it holds — a count, not a timing.  (The parent's single
    shard copied every held key on every insert that ended over
    budget.)"""
    cap, step = 20_000, 10
    k = _Kind(kind, cap)
    inserts = 0
    for lo in range(0, 3 * cap, step):
        k.insert(lo, lo + step)
        inserts += 1
    st = k.vc.stats()
    evicted = k.m.counter("cache.evicted_entries")
    assert st["evicted_entries"] == evicted >= 2 * cap - step
    # inserts that ended over budget: thousands; generations retired: 5
    assert inserts == 6000 and st["rotations"] <= 3 * cap // (cap // 2)
    assert evicted >= (st["rotations"] - 1) * (cap // 2)
    assert st["entries"] == k.held() <= cap


@pytest.mark.parametrize("kind", ["rel", "col"])
def test_concurrent_lookups_and_inserts_keep_the_counts_exact(kind):
    """Eight threads look up and insert overlapping skewed keys while
    generations rotate under them (probes are lock-free, promotion and
    rotation are not): a hit is always the key's own verdict, and when
    they are done the counts are exactly what the generations hold."""
    import sys

    k = _Kind(kind, cap=600)
    vc, wrong = k.vc, []
    verdict = lambda i: i % 3 == 0

    def worker(w):
        rng = np.random.default_rng(w)
        cdf = np.cumsum(1.0 / np.arange(1, 4001))
        for _ in range(150):
            ids = np.searchsorted(cdf, rng.random(24) * cdf[-1]).tolist()
            if kind == "rel":
                ks = [((f"repo:r{i}", "read", "user:u"), vcache.EMPTY_CTX_FP)
                      for i in ids]
                got = [None if v is None else v[0]
                       for v in vc.lookup_rels(1, ks)]
                vc.insert_rels(1, [(key, verdict(i)) for key, i, g
                                   in zip(ks, ids, got) if g is None], 9)
            else:
                ks = np.array(ids, np.int64)
                arr = vc.lookup_cols(1, ks)
                got = [None] * 24 if arr is None else [
                    None if v < 0 else bool(v & 1) for v in arr.tolist()]
                miss = [j for j, g in enumerate(got) if g is None]
                vc.insert_cols(1, ks[miss], [verdict(ids[j]) for j in miss], 9)
            wrong.extend(i for i, g in zip(ids, got)
                         if g is not None and g != verdict(i))

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    st = vc.stats()
    assert st["entries"] == k.held() and st["rotations"] >= 2
    assert st["bytes"] == st["entries"] * k.entry_bytes <= st["max_bytes"]
    assert k.m.counter("cache.hits") > 0
