"""Host lowering of a check batch (``DeviceEngine._lower_queries``): the
int32 query columns against a per-key reference, and concurrent callers
against one, on both interners."""

import sys
import threading

import numpy as np
import pytest

from gochugaru_tpu import native, rel
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.rel.relationship import WILDCARD_ID
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot

SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition doc {
  relation reader: user | user:* | group#member
  permission read = reader
}
"""

INTERNERS = ["python"] + (["native"] if native.available() else [])


def _world(kind):
    if kind == "native":
        from gochugaru_tpu.native.interner import NativeInterner

        interner = NativeInterner()
    else:
        interner = Interner()
    cs = compile_schema(parse_schema(SCHEMA))
    rels = [rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i % 7}")
            for i in range(30)]
    rels += [
        rel.must_from_triple("doc:d1", "reader", "user:*"),
        rel.must_from_triple("doc:d2", "reader", "group:g0#member"),
        rel.must_from_triple("group:g0", "member", "user:u1"),
        rel.must_from_triple("group:g1", "member", "group:g0#member"),
    ]
    snap = build_snapshot(1, cs, interner, rels, epoch_us=1_700_000_000_000_000)
    return DeviceEngine(cs, EngineConfig.for_schema(cs)), snap


def _reference_columns(engine, snap, rels):
    """The lowering one key at a time, through ``lookup`` and
    ``type_lookup``."""
    it, slot_of = snap.interner, engine.compiled.slot_of_name
    wc_of = snap.wildcard_node_of_type
    rows = []
    for r in rels:
        res = it.lookup(r.resource_type, r.resource_id)
        srel = slot_of.get(r.subject_relation, -1) if r.subject_relation else -1
        if r.subject_relation and srel < 0:
            res = -1  # an unknown subject relation is never granted
        stid = it.type_lookup(r.subject_type)
        wc = -1
        if 0 <= stid < len(wc_of) and r.subject_id != WILDCARD_ID:
            wc = int(wc_of[stid])
        is_self = bool(r.subject_relation) and (
            (r.resource_type, r.resource_id, r.resource_relation)
            == (r.subject_type, r.subject_id, r.subject_relation))
        rows.append((res, slot_of.get(r.resource_relation, -1),
                     it.lookup(r.subject_type, r.subject_id), srel, wc, is_self))
    names = ("q_res", "q_perm", "q_subj", "q_srel", "q_wc", "q_self")
    return {k: [row[j] for row in rows] for j, k in enumerate(names)}


MIXED = [
    rel.must_from_triple("doc:d3", "read", "user:u3"),
    rel.must_from_triple("group:g0", "member", "user:u1"),      # another type
    rel.must_from_triple("doc:nope", "read", "user:u3"),        # unknown resource
    rel.must_from_triple("doc:d3", "read", "user:nobody"),      # unknown subject
    rel.must_from_triple("doc:d2", "read", "group:g0#nosuch"),  # unknown subject relation
    rel.must_from_triple("doc:d2", "read", "group:g0#member"),
    rel.must_from_triple("doc:d1", "read", "user:*"),           # wildcard subject
    rel.must_from_triple("group:g0", "member", "group:g0#member"),  # self-reference
    rel.must_from_triple("ghost:x", "read", "user:u1"),         # unknown types
    rel.must_from_triple("doc:d1", "nosuch", "ghost:y"),
    rel.must_from_triple("doc:d4", "read", "user:u4"),
]


@pytest.mark.parametrize("kind", INTERNERS)
def test_lowered_columns_equal_a_per_key_reference(kind):
    engine, snap = _world(kind)
    queries, uniq, _ = engine._lower_queries(snap, MIXED)
    want = _reference_columns(engine, snap, MIXED)
    for k, col in want.items():
        assert queries[k].tolist() == col, k
        assert queries[k].dtype == (bool if k == "q_self" else np.int32), k
    # the rows the reference is there for
    assert want["q_res"][4] == -1 and want["q_subj"][4] >= 0
    assert want["q_wc"][0] >= 0 and want["q_wc"][6] == -1
    assert want["q_self"] == [i == 7 for i in range(len(MIXED))]
    assert (uniq[queries["q_row"]][:, 0] == queries["q_subj"]).all()
    empty, uniq0, _ = engine._lower_queries(snap, [])
    assert all(v.shape == (0,) for v in empty.values()) and len(uniq0) == 0


@pytest.mark.parametrize("kind", INTERNERS)
def test_concurrent_lowering_with_a_writer_equals_one_thread(kind):
    """Four threads lower different batches at once while a fifth interns
    new nodes (the native table grows under them): each gets the columns
    one thread gets."""
    engine, snap = _world(kind)
    # the native table (65,536 slots) rehashes at 0.7 load: start just
    # under it, so that the writer's first few hundred nodes cross it
    for i in range(45_600 - len(snap.interner)):
        snap.interner.node("user", f"pre{i}")
    batches = [
        [MIXED[(i + k) % len(MIXED)] for i in range(400)]
        + [rel.must_from_triple(f"doc:d{(i * 7 + k) % 40}", "read",
                                f"user:u{(i + k) % 9}") for i in range(1600)]
        for k in range(4)
    ]
    want = [engine._lower_queries(snap, b)[0] for b in batches]
    got = [[] for _ in batches]
    errors = []
    stop = threading.Event()

    def lower(k):
        try:
            for _ in range(6):
                got[k].append(engine._lower_queries(snap, batches[k])[0])
        except Exception as e:  # surfaced below
            errors.append(e)

    def write():
        i = 0
        while not stop.is_set() and i < 120_000:
            snap.interner.node("user", f"new{i}")
            i += 1

    writer = threading.Thread(target=write)
    threads = [threading.Thread(target=lower, args=(k,)) for k in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        writer.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        writer.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads + [writer])
    assert not errors, errors
    assert len(snap.interner) > 46_000, "the writer never grew the table"
    for k, runs in enumerate(got):
        assert len(runs) == 6
        for q in runs:
            for name, col in want[k].items():
                assert np.array_equal(q[name], col), (k, name)
