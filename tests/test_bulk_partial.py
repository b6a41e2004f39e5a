"""Bulk-Check per-item error parity.

The reference's Check maps CheckBulkPermissions pairs in order and, on a
per-item error, aborts returning the results accumulated so far plus the
error (/root/reference/client/client.go:279-283).  Locally the per-item
work is the host-oracle resolution of conditional/overflowed items — an
exception there must surface as BulkCheckItemError carrying the partial
prefix, and must NOT be retried (the reference retries the RPC, not the
mapping loop).
"""

import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import Client
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import BulkCheckItemError

SCHEMA = """
caveat tier(t int, min int) { t >= min }
definition user {}
definition doc {
    relation reader: user | user with tier
    permission read = reader
}
"""


def _client() -> Client:
    c = Client()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("doc:a", "reader", "user:u1"))
    # caveated rows force host-oracle resolution (conditional plane)
    txn.touch(
        rel.must_from_triple("doc:b", "reader", "user:u2").with_caveat(
            "tier", {"min": 3}
        )
    )
    txn.touch(
        rel.must_from_triple("doc:c", "reader", "user:u3").with_caveat(
            "tier", {"min": 3}
        )
    )
    c.write(ctx, txn)
    return c


def test_per_item_error_returns_partials():
    c = _client()
    ctx = background()
    cs = consistency.full()
    checks = [
        rel.must_from_triple("doc:a", "read", "user:u1"),  # definite T
        # no query context: the device CEL VM yields UNKNOWN → host
        rel.must_from_triple("doc:b", "read", "user:u2"),
        rel.must_from_triple("doc:c", "read", "user:u3"),  # made to fail
        rel.must_from_triple("doc:a", "read", "user:u9"),  # never reached
    ]
    # baseline: conditional items resolve (to not-granted) on the host
    assert c.check(ctx, cs, *checks) == [True, False, False, False]

    # fail the SECOND host resolution (item index 2)
    real_oracle_for = c._oracle_for
    boom = RuntimeError("caveat evaluation exploded")

    def failing_oracle_for(snap):
        oracle = real_oracle_for(snap)

        class Wrapper:
            def __init__(self):
                self.calls = 0

            def check_relationship(self, r):
                self.calls += 1
                if self.calls == 2:
                    raise boom
                return oracle.check_relationship(r)

        return Wrapper()

    c._oracle_for = failing_oracle_for
    with pytest.raises(BulkCheckItemError) as ei:
        c.check(ctx, cs, *checks)
    err = ei.value
    # results up to (not including) the failing item, reference order
    assert err.index == 2
    assert err.results == [True, False]
    assert err.__cause__ is boom


def test_per_item_error_not_retried():
    c = _client()
    ctx = background()
    cs = consistency.full()
    check = rel.must_from_triple("doc:b", "read", "user:u2")
    calls = {"n": 0}
    real_oracle_for = c._oracle_for

    def failing_oracle_for(snap):
        class Wrapper:
            def check_relationship(self, r):
                calls["n"] += 1
                raise RuntimeError("always fails")

        return Wrapper()

    c._oracle_for = failing_oracle_for
    with pytest.raises(BulkCheckItemError):
        c.check(ctx, cs, check)
    assert calls["n"] == 1, "per-item mapping errors must not be retried"
    c._oracle_for = real_oracle_for
    assert c.check(ctx, cs, check) == [False]


def test_pipelined_subbatch_matches_monolithic():
    """check_batch with flat_pipeline_batch splits big batches into
    queued sub-dispatches; results must be identical to the monolithic
    dispatch (VERDICT r04 item 8)."""
    import dataclasses

    import numpy as np

    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.engine.plan import EngineConfig
    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner
    from gochugaru_tpu.store.snapshot import build_snapshot
    from gochugaru_tpu import rel

    cs = compile_schema(parse_schema("""
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """))
    rels = [
        rel.must_from_tuple(f"doc:d{i % 40}#reader", f"user:u{i % 9}")
        for i in range(120)
    ]
    snap = build_snapshot(1, cs, Interner(), rels, epoch_us=1_700_000_000_000_000)
    checks = [
        rel.must_from_triple(f"doc:d{i % 50}", "read", f"user:u{i % 11}")
        for i in range(100)
    ]
    eng_m = DeviceEngine(cs, EngineConfig.for_schema(cs, flat_pipeline_batch=0))
    eng_p = DeviceEngine(cs, EngineConfig.for_schema(cs, flat_pipeline_batch=16))
    dm = eng_m.prepare(snap)
    dp = eng_p.prepare(snap)
    NOW = 1_700_000_000_000_000
    d0, p0, o0 = eng_m.check_batch(dm, checks, now_us=NOW)
    d1, p1, o1 = eng_p.check_batch(dp, checks, now_us=NOW)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(p0), np.asarray(p1))
    assert np.array_equal(np.asarray(o0), np.asarray(o1))

    # the generator form: per-sub-batch windows in order, same planes
    queries, _qc = eng_p._lower_queries(snap, checks, dp.strings)
    got = list(eng_p.check_columns_pipelined(
        dp, queries["q_res"], queries["q_perm"], queries["q_subj"],
        now_us=NOW, sub_batch=16,
    ))
    assert [g[0] for g in got] == list(range(0, 100, 16))
    dcat = np.concatenate([g[2] for g in got])
    assert np.array_equal(dcat, np.asarray(d0))
