"""Host lowering of a check batch (``DeviceEngine._lower_queries``): the
int32 query columns against a per-key reference, concurrent callers
against one, on both interners, and the subject-row table of the
two-phase programs (``subject_rows``), which the lowering no longer
builds."""

import sys
import threading

import numpy as np
import pytest

from gochugaru_tpu import native, rel
from gochugaru_tpu.engine.device import DeviceEngine, subject_rows
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.rel.relationship import WILDCARD_ID
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import metrics

SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition doc {
  relation reader: user | user:* | group#member
  permission read = reader
}
"""

INTERNERS = ["python"] + (["native"] if native.available() else [])


def _world(kind, **config):
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
    return DeviceEngine(cs, EngineConfig.for_schema(cs, **config)), snap


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
    # self-references that the nodes cannot settle (-1 == -1 proves
    # nothing): unknown nodes, and a relation with no slot on both sides
    rel.must_from_triple("ghost:x", "member", "ghost:x#member"),
    rel.must_from_triple("ghost:x", "member", "ghost:y#member"),
    rel.must_from_triple("ghost:x", "member", "spectre:x#member"),
    rel.must_from_triple("group:g0", "nosuch", "group:g0#nosuch"),
    rel.must_from_triple("group:g0", "nosuch", "group:g0#nosuch2"),
    rel.must_from_triple("group:g0", "member", "group:g1#member"),  # same slot, other node
    rel.must_from_triple("group:g0", "read", "group:g0#member"),    # same node, other slot
    # an unknown resource relation beside a known subject relation
    rel.must_from_triple("doc:d2", "nosuch", "group:g0#member"),
    # wildcard subjects whose type has no wildcard node (wc_of is -1) or
    # is outside the schema: q_subj and q_wc are both -1, never equal nodes
    rel.must_from_triple("doc:d1", "read", "group:*"),
    rel.must_from_triple("doc:d1", "read", "ghost:*"),
]

SELF_ROWS = {7, 11, 14}

BATCHES = {
    "mixed": MIXED,
    "empty": [],
    "one": MIXED[5:6],
    "one-self": MIXED[7:8],
    "one-unknown-self": MIXED[11:12],
    "one-unknown-subject-relation": MIXED[4:5],
    "one-wildcard": MIXED[6:7],
    "one-wildcard-no-node": MIXED[19:20],
    "one-wildcard-unknown-type": MIXED[20:21],
}


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("kind", INTERNERS)
def test_lowered_columns_equal_a_per_key_reference(kind, batch):
    engine, snap = _world(kind)
    rels = BATCHES[batch]
    queries, _ = engine._lower_queries(snap, rels)
    want = _reference_columns(engine, snap, rels)
    assert set(queries) == set(want) | {"q_ctx"}
    for k, col in want.items():
        assert queries[k].tolist() == col, k
        assert queries[k].dtype == (bool if k == "q_self" else np.int32), k
    assert queries["q_ctx"].tolist() == [-1] * len(rels)


def test_the_reference_settles_the_rows_it_is_there_for():
    engine, snap = _world("python")
    want = _reference_columns(engine, snap, MIXED)
    assert want["q_res"][4] == -1 and want["q_subj"][4] >= 0
    assert want["q_wc"][0] >= 0 and want["q_wc"][6] == -1
    assert want["q_self"] == [i in SELF_ROWS for i in range(len(MIXED))]
    assert want["q_res"][11] == want["q_subj"][11] == -1
    assert want["q_perm"][18] == -1 and want["q_srel"][18] >= 0 and want["q_res"][18] >= 0
    for i in (19, 20):  # group:* and ghost:*: no wildcard node to be or to add
        assert want["q_subj"][i] == want["q_wc"][i] == -1 and want["q_res"][i] >= 0
    wc_of = snap.wildcard_node_of_type
    assert wc_of[snap.interner.type_lookup("group")] == -1
    assert snap.interner.type_lookup("ghost") == -1


def _mixed_key():
    engine, snap = _world("python")
    q, _ = engine._lower_queries(snap, MIXED + MIXED[::2])
    return np.stack([q["q_subj"], q["q_srel"], q["q_wc"], q["q_ctx"]], axis=1)


def _random_key():
    rng = np.random.default_rng(28)
    key = rng.integers(-1, 5, (4000, 4)).astype(np.int32)
    key[::7, 0] = rng.integers(-1, 2**31 - 1, key[::7].shape[0])
    return key


@pytest.mark.parametrize("make_key", [
    _mixed_key, _random_key,
    lambda: np.zeros((0, 4), np.int32),
    lambda: np.full((1, 4), -1, np.int32),
], ids=["mixed", "random", "empty", "one"])
def test_subject_rows_equal_numpys_unique_over_rows(make_key):
    key = make_key()
    want_rows, want_inverse = np.unique(key, axis=0, return_inverse=True)
    rows, q_row = subject_rows(*key.T)
    assert rows.dtype == q_row.dtype == np.int32
    assert rows.shape == want_rows.shape and q_row.shape == (len(key),)
    assert np.array_equal(rows, want_rows)
    assert np.array_equal(q_row, want_inverse.ravel())
    assert np.array_equal(rows[q_row], key)


NOW_US = 1_700_000_000_000_000


def _answer(engine, dsnap, snap, door):
    if door == "check_batch":
        return engine.check_batch(dsnap, MIXED, now_us=NOW_US)
    # the columnar door derives q_self from the columns alone: the rows
    # whose strings decide it are check_batch's
    q, _ = engine._lower_queries(snap, MIXED[:11])
    return engine.check_columns(
        dsnap, q["q_res"], q["q_perm"], q["q_subj"], q_srel=q["q_srel"],
        q_wc=q["q_wc"], now_us=NOW_US)


@pytest.mark.parametrize("door", ["check_batch", "check_columns"])
def test_only_the_two_phase_dispatch_builds_the_subject_rows(door):
    """A ``use_flat=False`` engine answers the mixed batch as the flat
    engine does, and ``engine.subject_rows`` moves on it alone."""
    answers = {}
    for use_flat in (True, False):
        engine, snap = _world("python", use_flat=use_flat)
        dsnap = engine.prepare(snap)
        assert (dsnap.flat_meta is not None) == use_flat
        before = metrics.default.counter("engine.subject_rows")
        answers[use_flat] = [
            np.asarray(a) for a in _answer(engine, dsnap, snap, door)]
        built = metrics.default.counter("engine.subject_rows") - before
        assert built == (0 if use_flat else 1)
    for flat, legacy in zip(answers[True], answers[False]):
        assert np.array_equal(flat, legacy)
    definite = answers[True][0]
    assert definite[[0, 1, 5, 6, 7]].all() and not definite[[2, 3, 4, 8, 9]].any()
    if door == "check_batch":
        assert not definite[[19, 20]].any()
        assert definite[sorted(SELF_ROWS)].all()


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
