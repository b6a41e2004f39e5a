"""Client cache-pinning (round-2 Weak #5) and the check's stages inside a
jax.profiler session (SURVEY.md §5 tracing/profiling)."""

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import Client
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background

SCHEMA = """
definition user {}
definition doc {
    relation reader: user
    permission view = reader
}
"""


def seeded_client():
    c = Client()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    txn.create(rel.must_from_triple("doc:d", "reader", "user:u"))
    rev = c.write(ctx, txn)
    return c, ctx, rev


def test_snapshot_pinned_reader_survives_head_writes():
    c, ctx, rev = seeded_client()
    pinned = consistency.snapshot(rev)
    assert c.check_one(ctx, pinned, rel.must_from_triple("doc:d", "view", "user:u"))
    snap = c._store.snapshot_for(pinned)
    held = c._dsnap_cache[snap.revision]
    for i in range(10):
        txn = rel.Txn()
        txn.create(rel.must_from_triple(f"doc:w{i}", "reader", f"user:x{i}"))
        c.write(ctx, txn)
        # a head reader churns the cache with fresh revisions…
        assert c.check_one(
            ctx, consistency.full(),
            rel.must_from_triple(f"doc:w{i}", "view", f"user:x{i}"),
        )
        # …but the pinned generation stays warm: same prepared object
        assert c.check_one(
            ctx, pinned, rel.must_from_triple("doc:d", "view", "user:u")
        )
        assert c._dsnap_cache.get(snap.revision) is held, (
            f"pinned generation evicted after write {i}"
        )
    assert len(c._dsnap_cache) <= Client.SNAPSHOT_CACHE_MAX


def test_lowest_revision_not_preferentially_evicted():
    c, ctx, rev = seeded_client()
    pinned = consistency.snapshot(rev)
    c.check_one(ctx, pinned, rel.must_from_triple("doc:d", "view", "user:u"))
    snap = c._store.snapshot_for(pinned)
    for i in range(6):
        txn = rel.Txn()
        txn.create(rel.must_from_triple(f"doc:y{i}", "reader", "user:u"))
        c.write(ctx, txn)
        c.check_one(
            ctx, consistency.full(),
            rel.must_from_triple(f"doc:y{i}", "view", "user:u"),
        )
        c.check_one(ctx, pinned, rel.must_from_triple("doc:d", "view", "user:u"))
    # the oracle cache follows the same LRU policy
    assert snap.revision in c._dsnap_cache


def test_check_inside_a_jax_profiler_trace_holds_the_stages(tmp_path):
    """The profiling hook is JAX's own: wrap the window in
    ``jax.profiler.trace(dir)`` and the check's stages are events of that
    trace (``gochugaru.<layer>.<stage>``), leaves on their thread."""
    import jax

    from tests.test_trace import (
        assert_stages_are_leaves,
        profiled_stage_events,
        stage_names_on_thread_of,
    )

    c, ctx, rev = seeded_client()
    r = rel.must_from_triple("doc:d", "view", "user:u")
    assert c.check_one(ctx, consistency.at_least(rev), r)  # prepare, compile
    before = metrics.default.snapshot().get("checks.device_time_s.count", 0)
    with jax.profiler.trace(str(tmp_path / "trace")):
        assert c.check_one(ctx, consistency.at_least(rev), r)
    events = profiled_stage_events(tmp_path / "trace")
    names = stage_names_on_thread_of(events, "gochugaru.engine.lower")
    assert names == [
        "gochugaru.client.snapshot", "gochugaru.engine.lower",
        "gochugaru.engine.enqueue", "gochugaru.engine.fetch",
        "gochugaru.client.verdicts",
    ], names
    assert_stages_are_leaves(events)
    after = metrics.default.snapshot().get("checks.device_time_s.count", 0)
    assert after == before + 1


def test_client_takes_incremental_device_path():
    """Consecutive write→check revisions through the public Client must
    advance the device snapshot incrementally (base tables reused, delta
    overlay only) — the Watch-driven re-index path, BASELINE config 5."""
    c, ctx, rev = seeded_client()
    full = consistency.full()
    assert c.check_one(ctx, full, rel.must_from_triple("doc:d", "view", "user:u"))
    incremental = 0
    for i in range(4):
        txn = rel.Txn()
        txn.touch(rel.must_from_triple("doc:d", "reader", f"user:w{i}"))
        c.write(ctx, txn)
        assert c.check_one(
            ctx, full, rel.must_from_triple("doc:d", "view", f"user:w{i}")
        )
        snap = c._store.snapshot_for(full)
        ds = c._dsnap_cache.get(snap.revision)
        if (
            ds is not None
            and ds.flat_meta is not None
            and ds.flat_meta.delta is not None
        ):
            incremental += 1
    assert incremental >= 3, f"incremental prepares: {incremental}/4"
    # deletes ride the same path (tombstone overlay)
    txn = rel.Txn()
    txn.delete(rel.must_from_triple("doc:d", "reader", "user:w0"))
    c.write(ctx, txn)
    assert not c.check_one(
        ctx, full, rel.must_from_triple("doc:d", "view", "user:w0")
    )
    snap = c._store.snapshot_for(full)
    ds = c._dsnap_cache.get(snap.revision)
    assert ds is not None and ds.flat_meta.delta is not None
    assert ds.flat_meta.delta.has_tombs
