"""Revision-pinned verdict cache + serving dedup (engine/vcache.py):
key packing exactness, byte-bounded revision-shard LRU, the consistency
strategies as read policy, the delta-chain zero-stale guarantee across
all four strategies, the live-context caveat exclusion, pinned now_us on
time-gated entries, in-batch dedup parity, the singleflight dispatch
window (park/fan-out/failure), chaos with ``cache.lookup`` armed, and
cache-off bitwise behavior."""

import threading
import time

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_host_only_evaluation,
    with_latency_mode,
    with_store,
    with_verdict_cache,
)
from gochugaru_tpu.engine import vcache
from gochugaru_tpu.serve import MicroBatcher, ServeConfig
from gochugaru_tpu.utils import faults, metrics
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import BulkCheckItemError, UnavailableError

CTX = background()
ALL_CS = ("full", "min_latency", "at_least", "snapshot")


def _strategy(name, rev_token):
    if name == "full":
        return consistency.full()
    if name == "min_latency":
        return consistency.min_latency()
    if name == "at_least":
        return consistency.at_least(rev_token)
    return consistency.snapshot(rev_token)


def _world(*opts):
    """RBAC world through a store-backed client + host-only oracle
    client sharing the store."""
    c = new_tpu_evaluator(with_latency_mode(), *opts)
    c.write_schema(CTX, """
    definition user {}
    definition org { relation admin: user  relation member: user }
    definition repo {
        relation org: org
        relation reader: user
        permission admin = org->admin
        permission read = reader + admin + org->member
    }
    """)
    rng = np.random.default_rng(11)
    txn = rel.Txn()
    for i in range(120):
        txn.touch(rel.must_from_triple(
            f"repo:r{i}", "reader", f"user:u{rng.integers(60)}"))
        txn.touch(rel.must_from_triple(f"repo:r{i}", "org", f"org:o{i % 3}"))
    for o in range(3):
        txn.touch(rel.must_from_triple(f"org:o{o}", "admin", f"user:u{o}"))
        txn.touch(rel.must_from_triple(
            f"org:o{o}", "member", f"user:u{o + 10}"))
    rev = c.write(CTX, txn)
    oracle = new_tpu_evaluator(with_host_only_evaluation(),
                               with_store(c.store))
    return c, oracle, rev


def _checks(rng, n):
    return [rel.must_from_triple(
        f"repo:r{rng.integers(120)}", "read", f"user:u{rng.integers(60)}")
        for _ in range(n)]


# ---------------------------------------------------------------------------
# keys / packing
# ---------------------------------------------------------------------------

def test_pack_cols_exact_int64_and_tuple_fallback():
    p = np.array([3, 3, 7], np.int32)
    r = np.array([10, 10, 99], np.int32)
    s = np.array([5, 5, 5], np.int32)
    k = vcache.pack_cols(p, r, s)
    assert isinstance(k, np.ndarray) and k.dtype == np.int64
    assert k[0] == k[1] != k[2]
    # scalar pack matches the vectorized layout exactly
    assert vcache.pack_one(3, 10, 5) == int(k[0])
    # distinct triples can never alias under the exact pack
    assert len({int(x) for x in k}) == 2
    # ids past the pack bounds degrade to exact tuples, not wrong ints
    big = np.array([1 << 25, 7], np.int32)
    kt = vcache.pack_cols(np.array([1, 1], np.int32), big,
                          np.array([2, 3], np.int32))
    assert isinstance(kt, list) and kt[0] == (1, 1 << 25, 2)
    assert vcache.pack_one(1, 1 << 25, 2) == (1, 1 << 25, 2)


def test_rel_key_and_context_fingerprint():
    r1 = rel.must_from_triple("repo:r1", "read", "user:u1")
    r2 = rel.must_from_triple("repo:r1", "read", "user:u1")
    assert vcache.rel_key(r1) == vcache.rel_key(r2)
    assert vcache.rel_key(r1)[1] == vcache.EMPTY_CTX_FP
    rc = r1.with_caveat("c", {"tier": 3})
    rc2 = r1.with_caveat("c", {"tier": 3})
    rc3 = r1.with_caveat("c", {"tier": 4})
    assert vcache.rel_key(rc) == vcache.rel_key(rc2)
    assert vcache.rel_key(rc)[1] != vcache.EMPTY_CTX_FP
    assert vcache.rel_key(rc) != vcache.rel_key(rc3)


# ---------------------------------------------------------------------------
# VerdictCache structure
# ---------------------------------------------------------------------------

def test_cache_lookup_insert_and_snapshot_rebuild():
    m = metrics.Metrics()
    vc = vcache.VerdictCache(registry=m)
    rng = np.random.default_rng(0)
    keys = vcache.pack_cols(
        np.full(5000, 2, np.int32),
        rng.permutation(5000).astype(np.int32),
        rng.integers(0, 100, 5000).astype(np.int32),
    )
    verd = rng.random(5000) < 0.5
    vc.insert_cols(7, keys, verd, now_us=123)
    # rebuild threshold (1024) crossed → sorted snapshot + extra dict
    sh = vc._revs[7]["c"]
    assert sh.snap[0].shape[0] > 0
    arr = vc.lookup_cols(7, keys)
    assert ((arr >= 0)).all()
    assert ((arr & 1).astype(bool) == verd).all()
    assert (arr >> 1 == 123).all()  # pinned now_us rides every entry
    # misses at another revision; hit/miss counters add up
    assert vc.lookup_cols(8, keys) is None
    assert m.counter("cache.hits") == 5000
    assert m.counter("cache.misses") == 5000
    assert vc.get_col(7, int(vcache.keys_list(keys)[0])) == (
        bool(verd[0]), 123
    )


def test_cache_byte_bound_evicts_oldest_revision_shard():
    m = metrics.Metrics()
    vc = vcache.VerdictCache(
        max_bytes=vcache.VerdictCache.COL_ENTRY_BYTES * 1000, registry=m
    )
    for rev in range(1, 5):
        keys = np.arange(rev * 1000, rev * 1000 + 400, dtype=np.int64)
        vc.insert_cols(rev, keys, np.ones(400, bool), now_us=1)
    assert 1 not in vc.resident_revisions
    assert vc.stats()["bytes"] <= vc.max_bytes
    assert m.counter("cache.evicted_revisions") >= 1
    # most-recently-used revision survives
    assert 4 in vc.resident_revisions


def test_cache_drop_revision_structural_invalidation():
    vc = vcache.VerdictCache(registry=metrics.Metrics())
    keys = np.arange(10, dtype=np.int64)
    vc.insert_cols(3, keys, np.ones(10, bool), now_us=1)
    vc.drop_revision(3)
    assert vc.lookup_cols(3, keys) is None
    assert vc.stats()["entries"] == 0


def test_policy_for_maps_strategies():
    assert vcache.policy_for(consistency.full()) == vcache.CACHE_OFF
    assert vcache.policy_for(None) == vcache.CACHE_OFF
    for cs in (consistency.min_latency(), consistency.at_least("gtz1.1"),
               consistency.snapshot("gtz1.1")):
        assert vcache.policy_for(cs) == vcache.CACHE_RW


# ---------------------------------------------------------------------------
# client integration: read policy + revision keying
# ---------------------------------------------------------------------------

def test_cached_checks_hit_and_full_bypasses():
    c, oracle, rev = _world(with_verdict_cache())
    m = metrics.default
    rng = np.random.default_rng(1)
    qs = _checks(rng, 12)
    ml = consistency.min_latency()
    want = oracle.check(CTX, consistency.full(), *qs)
    assert c.check(CTX, ml, *qs) == want
    h0 = m.counter("cache.hits")
    assert c.check(CTX, ml, *qs) == want  # warm repeat
    assert m.counter("cache.hits") - h0 >= len(qs)
    # full() bypasses the cache entirely — no reads, no hits
    h1, mi1 = m.counter("cache.hits"), m.counter("cache.misses")
    assert c.check(CTX, consistency.full(), *qs) == want
    assert m.counter("cache.hits") == h1
    assert m.counter("cache.misses") == mi1


def test_delta_chain_zero_stale_verdicts_all_strategies():
    """Writes interleave with cached checks at all four consistency
    strategies: every verdict must equal the host oracle's at the SAME
    strategy (identical snapshot resolution), across the whole chain —
    revision-keyed reads only, zero stale verdicts."""
    c, oracle, rev0 = _world(with_verdict_cache())
    m = metrics.default
    rng = np.random.default_rng(2)
    qs = _checks(rng, 10)
    pinned = []  # (rev_token, verdicts at that revision)
    for round_i in range(6):
        # a write that flips real verdicts: toggle reader edges
        txn = rel.Txn()
        i = int(rng.integers(120))
        e = rel.must_from_triple(f"repo:r{i}", "reader",
                                 f"user:u{int(rng.integers(60))}")
        (txn.delete if round_i % 2 else txn.touch)(e)
        rev = c.write(CTX, txn)
        for name in ALL_CS:
            cs = _strategy(name, rev)
            got = c.check(CTX, cs, *qs)
            want = oracle.check(CTX, cs, *qs)
            assert got == want, (round_i, name)
            # repeat immediately — served warm, still exact
            assert c.check(CTX, cs, *qs) == want, (round_i, name, "warm")
        snap = c.store.snapshot_for(consistency.full())
        pinned.append((rev, c.check(CTX, consistency.snapshot(rev), *qs)))
        assert int(snap.revision) == int(rev.split(".")[-1])
    # pinned revisions still answer their own (historical) verdicts as
    # long as they stay resident — revision keying, not invalidation
    for rev, verdicts in pinned[-2:]:
        assert c.check(CTX, consistency.snapshot(rev), *qs) == verdicts
    assert m.counter("cache.hits") > 0


def test_min_latency_write_opens_fresh_keyspace():
    """A write mints a new revision; once the store serves it, cached
    verdicts from the previous revision are structurally unreachable —
    no stale read is possible through the cache."""
    c, oracle, _ = _world(with_verdict_cache())
    q = rel.must_from_triple("repo:r0", "read", "user:u55")
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("repo:r0", "reader", "user:u55"))
    c.write(CTX, txn)
    assert c.check(CTX, consistency.full(), q) == [True]
    ml = consistency.min_latency()
    assert c.check(CTX, ml, q) == [True]
    assert c.check(CTX, ml, q) == [True]  # cached at this revision
    txn = rel.Txn()
    txn.delete(rel.must_from_triple("repo:r0", "reader", "user:u55"))
    rev = c.write(CTX, txn)
    # full() materializes the new head; the cached True at the old
    # revision must not leak into the new revision's reads
    assert c.check(CTX, consistency.full(), q) == [False]
    assert c.check(CTX, consistency.at_least(rev), q) == [False]
    assert c.check(CTX, consistency.min_latency(), q) == [False]


# ---------------------------------------------------------------------------
# caveats and time
# ---------------------------------------------------------------------------

def _caveat_world():
    c = new_tpu_evaluator(with_latency_mode(), with_verdict_cache())
    c.write_schema(CTX, """
    caveat tier_at_least(tier int, minimum int) { tier >= minimum }
    definition user {}
    definition doc {
        relation viewer: user with tier_at_least
        permission view = viewer
    }
    """)
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("doc:a", "viewer", "user:u1").with_caveat(
        "tier_at_least", {"minimum": 5}))
    txn.touch(rel.must_from_triple("doc:b", "viewer", "user:u2").with_caveat(
        "tier_at_least", {"minimum": 5, "tier": 9}))
    c.write(CTX, txn)
    return c


def test_live_context_caveat_never_served_from_cache():
    """A check whose caveat reads LIVE query context must never read or
    write the cache — repeated identical context-bearing checks show no
    hits, and flipping the context flips the verdict."""
    c = _caveat_world()
    m = metrics.default
    ml = consistency.min_latency()
    q_hi = rel.must_from_triple("doc:a", "view", "user:u1").with_caveat(
        "", {"tier": 7})
    q_lo = rel.must_from_triple("doc:a", "view", "user:u1").with_caveat(
        "", {"tier": 3})
    h0 = m.counter("cache.hits")
    for _ in range(3):
        assert c.check(CTX, ml, q_hi) == [True]
        assert c.check(CTX, ml, q_lo) == [False]
    assert m.counter("cache.hits") == h0, "live-context verdict was cached"
    assert m.counter("cache.bypass") > 0


def test_context_free_caveat_outcome_caches():
    """Context-free caveat outcomes (stored context decides, or missing
    context → no grant) cache normally with a pinned now_us."""
    c = _caveat_world()
    m = metrics.default
    ml = consistency.min_latency()
    # doc:b's stored context is complete → definite, context-free
    qb = rel.must_from_triple("doc:b", "view", "user:u2")
    # doc:a without context → caveat cannot pass → definite False
    qa = rel.must_from_triple("doc:a", "view", "user:u1")
    assert c.check(CTX, ml, qb, qa) == [True, False]
    h0 = m.counter("cache.hits")
    assert c.check(CTX, ml, qb, qa) == [True, False]
    assert m.counter("cache.hits") - h0 == 2


def test_expiring_edge_verdict_pins_now_us():
    import datetime as dt

    c = new_tpu_evaluator(with_latency_mode(), with_verdict_cache())
    c.write_schema(CTX, """
    definition user {}
    definition doc { relation viewer: user  permission view = viewer }
    """)
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("doc:x", "viewer", "user:u1")
              .with_expiration(dt.datetime.now(dt.timezone.utc)
                               + dt.timedelta(hours=1)))
    c.write(CTX, txn)
    ml = consistency.min_latency()
    q = rel.must_from_triple("doc:x", "view", "user:u1")
    t0 = int(time.time() * 1_000_000)
    assert c.check(CTX, ml, q) == [True]
    snap = c.store.snapshot_for(ml)
    entry = c._vcache._revs[snap.revision]["r"][vcache.rel_key(q)]
    # the entry records the evaluation-time pin (LookupCursor
    # discipline): a later hit serves the verdict AS OF that time
    assert abs(entry[1] - t0) < 60_000_000
    h0 = metrics.default.counter("cache.hits")
    assert c.check(CTX, ml, q) == [True]
    assert metrics.default.counter("cache.hits") == h0 + 1


# ---------------------------------------------------------------------------
# dedup: in-batch + the singleflight window
# ---------------------------------------------------------------------------

def test_columns_dedup_parity_and_batch_dups_counter():
    c, oracle, _ = _world(with_verdict_cache())
    m = metrics.default
    snap = c.store.snapshot_for(consistency.full())
    inter = snap.interner
    slot = snap.compiled.slot_of_name["read"]
    rng = np.random.default_rng(3)
    user_pool = [n for i in range(60)
                 if (n := inter.lookup("user", f"u{i}")) >= 0]
    res = np.array([inter.lookup("repo", f"r{i}")
                    for i in rng.integers(0, 120, 64)], np.int32)
    subj = np.array([user_pool[i]
                     for i in rng.integers(0, len(user_pool), 64)], np.int32)
    res = np.tile(res, 4)  # heavy duplication
    subj = np.tile(subj, 4)
    perm = np.full(res.shape[0], slot, np.int32)
    d0 = m.counter("dedup.batch_dups")
    got = c._evaluate_columns(
        snap, res, perm, subj, latency=True,
        cs=consistency.min_latency(), dedup=True,
    )
    assert m.counter("dedup.batch_dups") - d0 >= 192
    want = np.fromiter(
        (c._check_interned(c._oracle_for(snap), snap, res[i], perm[i],
                           subj[i]) for i in range(res.shape[0])),
        bool, count=res.shape[0],
    )
    assert (got == want).all()


def test_bulk_item_error_remaps_to_caller_space():
    c, _, _ = _world(with_verdict_cache())
    snap = c.store.snapshot_for(consistency.full())
    q = np.arange(8, dtype=np.int32)
    dup = np.concatenate([q, q])  # 16 rows → 8 unique

    def boom(snap_, r, p, s, latency, span=None):
        # unique-space failure at index 3 with 3 resolved results
        raise BulkCheckItemError(3, np.array([True, False, True]),
                                 RuntimeError("x"))

    c._evaluate_columns_direct = boom
    with pytest.raises(BulkCheckItemError) as ei:
        c._evaluate_columns(
            snap, dup, np.zeros(16, np.int32), dup, latency=False,
            cs=consistency.min_latency(), dedup=True,
        )
    e = ei.value
    # caller-space: the reported prefix is fully resolved and the index
    # points at the first unresolved caller row
    assert e.index == 3
    assert len(e.results) == 3


def test_singleflight_window_park_and_fanout_cols():
    m = metrics.Metrics()
    sf = vcache.Singleflight(registry=m)
    keys = np.array([10, 20, 30, 40], np.int64)
    sf.open_cols(keys, np.sort(keys))
    assert sf.active
    assert sf.probe(20) and not sf.probe(99)
    from gochugaru_tpu.serve.batcher import SubmitFuture

    fut = SubmitFuture(time.perf_counter())
    assert sf.try_park(np.array([30, 10], np.int64), fut, "cols", 2)
    # partial overlap refuses to park
    fut2 = SubmitFuture(time.perf_counter())
    assert not sf.try_park(np.array([30, 99], np.int64), fut2, "cols", 2)
    verdicts = np.array([True, False, True, False])
    assert sf.close(verdicts, None, time.perf_counter()) == 1
    out = fut.result(timeout=1.0)
    assert out.tolist() == [True, True]  # rows 30→True, 10→True
    assert not sf.active
    assert m.counter("serve.dedup_parked") == 2
    assert m.counter("serve.checks") == 2


def test_singleflight_window_failure_rejects_retriable():
    sf = vcache.Singleflight(registry=metrics.Metrics())
    km = {vcache.rel_key(rel.must_from_triple("a:1", "r", "b:2")): 0}
    sf.open_map(km)
    from gochugaru_tpu.serve.batcher import SubmitFuture

    fut = SubmitFuture(time.perf_counter())
    assert sf.try_park(list(km.keys()), fut, "rels", 1)
    sf.close(None, UnavailableError("twin failed"), time.perf_counter())
    with pytest.raises(UnavailableError):
        fut.result(timeout=1.0)


def test_serving_parks_duplicate_submission_on_inflight_batch():
    """End-to-end: a submission arriving while its twin's batch is
    mid-dispatch parks on the window and resolves from the same
    verdicts — no queue slot, no second dispatch."""
    release = threading.Event()
    entered = threading.Event()

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        entered.set()
        assert release.wait(5.0)
        return q_res > 0

    m = metrics.Metrics()
    b = MicroBatcher(
        tiers=(256, 1024, 4096), start=False, registry=m,
        dispatch_cols=dispatch_cols,
    )
    cols = (np.array([1, 0, 2], np.int32), np.array([0, 0, 0], np.int32),
            np.array([7, 8, 9], np.int32))
    f1 = b.submit_columns("a", *cols)
    batch = b.form_batch()
    t = threading.Thread(target=b.dispatch_batch, args=(batch,))
    t.start()
    assert entered.wait(5.0)
    # twin arrives mid-dispatch → parks (depth stays zero)
    f2 = b.submit_columns("b", *cols)
    assert b.depth == 0
    assert m.counter("serve.dedup_parked") == 3
    release.set()
    t.join(5.0)
    assert f1.result(timeout=5.0).tolist() == [True, False, True]
    assert f2.result(timeout=5.0).tolist() == [True, False, True]
    assert m.counter("serve.batches") == 1
    b.close()


def test_serving_window_failure_parked_future_retriable():
    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        entered.set()
        assert release.wait(5.0)
        raise UnavailableError("transient device fault")

    release = threading.Event()
    entered = threading.Event()
    m = metrics.Metrics()
    b = MicroBatcher(
        tiers=(256,), start=False, registry=m, dispatch_cols=dispatch_cols,
    )
    cols = (np.array([1], np.int32),) * 3
    f1 = b.submit_columns("a", *cols)
    batch = b.form_batch()
    t = threading.Thread(target=b.dispatch_batch, args=(batch,))
    t.start()
    assert entered.wait(5.0)
    f2 = b.submit_columns("b", *cols)
    release.set()
    t.join(5.0)
    with pytest.raises(UnavailableError):
        f1.result(timeout=5.0)
    with pytest.raises(UnavailableError):
        f2.result(timeout=5.0)
    b.close()


def test_full_strategy_handle_never_parks():
    c, _, _ = _world()
    h = c.with_serving(cs=consistency.full())
    try:
        assert h.batcher._sf is None  # Full must see its own head
    finally:
        h.close()
    h2 = c.with_serving(cs=consistency.min_latency())
    try:
        assert h2.batcher._sf is not None
    finally:
        h2.close()


def test_dedup_off_config_disables_all_of_it():
    """dedup=False keeps duplicate submissions off the parked-twin
    path.  The Singleflight window stays BUILT (the online controller
    toggles dedup by swapping the config — tune/controller.py), so the
    assertion is behavioral: a twin arriving mid-dispatch queues for
    its own dispatch instead of parking, and a live ``apply_config``
    swap re-arms parking without rebuilding the batcher."""
    release = threading.Event()
    entered = threading.Event()

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        entered.set()
        assert release.wait(5.0)
        return q_res > 0

    m = metrics.Metrics()
    b = MicroBatcher(
        tiers=(256, 1024, 4096), start=False, registry=m,
        dispatch_cols=dispatch_cols, config=ServeConfig(dedup=False),
    )
    cols = (np.array([1, 0, 2], np.int32), np.array([0, 0, 0], np.int32),
            np.array([7, 8, 9], np.int32))
    f1 = b.submit_columns("a", *cols)
    batch = b.form_batch()
    t = threading.Thread(target=b.dispatch_batch, args=(batch,))
    t.start()
    assert entered.wait(5.0)
    # twin arrives mid-dispatch → queues, no park, no shared verdicts
    f2 = b.submit_columns("b", *cols)
    assert b.depth == 3
    assert m.counter("serve.dedup_parked") == 0
    release.set()
    t.join(5.0)
    assert f1.result(timeout=5.0).tolist() == [True, False, True]
    b.dispatch_batch(b.form_batch())
    assert f2.result(timeout=5.0).tolist() == [True, False, True]
    assert m.counter("serve.batches") == 2

    # live re-arm: the same batcher parks once the config says dedup
    b.apply_config(ServeConfig(dedup=True))
    entered.clear()
    release.clear()
    f3 = b.submit_columns("a", *cols)
    batch = b.form_batch()
    t = threading.Thread(target=b.dispatch_batch, args=(batch,))
    t.start()
    assert entered.wait(5.0)
    f4 = b.submit_columns("b", *cols)
    assert b.depth == 0  # parked on f3's in-flight batch
    assert m.counter("serve.dedup_parked") == 3
    release.set()
    t.join(5.0)
    assert f3.result(timeout=5.0).tolist() == [True, False, True]
    assert f4.result(timeout=5.0).tolist() == [True, False, True]
    assert m.counter("serve.batches") == 3
    b.close()


# ---------------------------------------------------------------------------
# chaos + cache-off behavior
# ---------------------------------------------------------------------------

def test_chaos_soak_cache_lookup_and_dedup_fanout():
    """cache.lookup + batcher sites armed under concurrent duplicate-
    heavy serving load: oracle parity on every answer, zero lost or
    duplicated futures through the dedup fan-out (SubmitFuture asserts
    double-resolution; a hang would time out)."""
    c, oracle, _ = _world(with_verdict_cache())
    m = metrics.default
    pool = [_checks(np.random.default_rng(5), 6) for _ in range(10)]
    want = [oracle.check(CTX, consistency.full(), *qs) for qs in pool]
    mismatches = []
    with c.with_serving(cs=consistency.min_latency()) as h:
        with faults.default.armed("cache.lookup", probability=0.25,
                                  seed=3) as spec:
            with faults.default.armed("batcher.dispatch", probability=0.1,
                                      seed=4):
                def worker(w):
                    lr = np.random.default_rng(w)
                    for _ in range(12):
                        i = int(lr.integers(len(pool)))
                        got = h.check(CTX.with_timeout(60.0), *pool[i],
                                      client_id=w)
                        if list(got) != want[i]:
                            mismatches.append((w, i))

                ts = [threading.Thread(target=worker, args=(w,))
                      for w in range(6)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
    assert not mismatches
    assert spec.fired > 0, "cache.lookup never fired"
    assert m.counter("cache.hits") > 0


def test_cache_off_client_touches_no_cache_state():
    base = metrics.default.snapshot()
    c, oracle, _ = _world()  # no with_verdict_cache
    rng = np.random.default_rng(9)
    qs = _checks(rng, 8)
    want = oracle.check(CTX, consistency.full(), *qs)
    assert c.check(CTX, consistency.min_latency(), *qs) == want
    with c.with_serving(cs=consistency.min_latency(), cache=False,
                        config=ServeConfig(dedup=False)) as h:
        assert h.check(CTX, *qs) == want
    now = metrics.default.snapshot()
    for k in ("cache.hits", "cache.misses", "cache.puts", "dedup.batch_dups",
              "serve.dedup_parked"):
        assert now.get(k, 0) == base.get(k, 0), k
    assert c._vcache is None


def test_dsnap_eviction_drops_cache_shard():
    c, _, _ = _world(with_verdict_cache())
    ml = consistency.min_latency()
    q = rel.must_from_triple("repo:r1", "read", "user:u1")
    revs = []
    for i in range(c.SNAPSHOT_CACHE_MAX + 2):
        txn = rel.Txn()
        txn.touch(rel.must_from_triple(
            f"repo:r{i}", "reader", f"user:uev{i}"))
        revs.append(c.write(CTX, txn))
        c.check(CTX, consistency.full(), q)  # materialize + prepare
        c.check(CTX, consistency.at_least(revs[-1]), q)  # populate shard
    resident = c._vcache.resident_revisions
    first = int(revs[0].split(".")[-1])
    assert first not in resident, (
        "evicted dsnap revision kept its verdict shard"
    )


def test_perf_report_carries_cache_section():
    from gochugaru_tpu.utils import perf as _perf

    c, _, _ = _world(with_verdict_cache())
    c.check(CTX, consistency.min_latency(),
            rel.must_from_triple("repo:r1", "read", "user:u1"))
    rep = _perf.render_report()
    assert "vcache" in rep and rep["vcache"]["entries"] >= 1


def test_node_interned_by_a_later_write_is_found_by_the_next_check():
    c, oracle, _ = _world()
    q = rel.must_from_triple("repo:r1", "read", "user:u1")
    c.check(CTX, consistency.full(), q)
    # a NEW object interned by a later write must be found: nothing in
    # front of the interner remembers the earlier -1
    q2 = rel.must_from_triple("repo:r1", "read", "user:brand_new")
    assert c.check(CTX, consistency.full(), q2) == [False]
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("repo:r1", "reader", "user:brand_new"))
    c.write(CTX, txn)
    assert c.check(CTX, consistency.full(), q2) == [True]
