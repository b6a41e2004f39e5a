"""Host lowering of a check batch (``DeviceEngine._lower_queries``): the
int32 query columns against a per-key reference, concurrent callers
against one, on both interners and through both passes over the
``Relationship`` objects (the native pull of ``native/lower.cpp`` and the
Python pass it falls back to), the request contexts of a caveat world
through both (the native grouping and ``dedup_contexts``), and the
subject-row table of the two-phase programs (``subject_rows``), which the
lowering no longer builds."""

import ctypes
import dataclasses
import sys
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from gochugaru_tpu import native, rel
from gochugaru_tpu.engine.device import DeviceEngine, subject_rows
from gochugaru_tpu.native import lower as native_lower
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.rel.relationship import (
    WILDCARD_ID,
    Relationship,
    decoded_relationship,
)
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

#: the interner a world is built on, and which pass lowers its batches:
#: "python" the pure-Python interner (always the Python pass), "native"
#: the native interner and the native pull, "native-nopull" the native
#: interner with the pull's library missing (the Python pass)
INTERNERS = ["python"] + (
    ["native", "native-nopull"] if native.available() else [])


def _no_pull(monkeypatch, directory, source=None):
    """Put a lowering library in place whose source is ``source`` in
    ``directory`` (missing where None), so that the engine's lowering
    finds no pull and runs its Python pass."""
    if source is not None:
        (directory / "lower.cpp").write_text(source)
    monkeypatch.setattr(native, "_LOWER", native._Library(
        "lower", native._LOWER.flag_sets, ctypes.PyDLL, native._bind_lower,
        directory=str(directory)))


@pytest.fixture
def lowering(request, monkeypatch, tmp_path):
    """Sets up the pass a test's ``kind`` names; returns whether the
    native pull lowers its batches."""
    kind = request.node.callspec.params["kind"]
    if kind == "native-nopull":
        _no_pull(monkeypatch, tmp_path)
    return kind == "native" and native.lower_lib() is not None


def _world(kind, **config):
    if kind.startswith("native"):
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
        rel.must_from_triple("doc:dé", "reader", "user:ü"),
        rel.must_from_triple("doc:文書", "reader", "user:u1"),
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


class TaggedRelationship(Relationship):
    """A subclass, as a caller's own type may be."""


@dataclasses.dataclass(frozen=True)
class SlotRelationship:
    """An object with the six fields and no ``__dict__``."""

    __slots__ = ("resource_type", "resource_id", "resource_relation",
                 "subject_type", "subject_id", "subject_relation")
    resource_type: str
    resource_id: str
    resource_relation: str
    subject_type: str
    subject_id: str
    subject_relation: str


KEY_FIELDS = [f.name for f in dataclasses.fields(SlotRelationship)]


def _decoded(r):
    return decoded_relationship(
        *(getattr(r, k) for k in KEY_FIELDS), "", {}, None)


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
    # ids the interner holds and does not hold, outside ASCII
    "non-ascii": [
        rel.must_from_triple("doc:dé", "read", "user:ü"),
        rel.must_from_triple("doc:文書", "read", "user:u1"),
        rel.must_from_triple("doc:dé", "read", "user:üü"),
        rel.must_from_triple("doc:d1", "read", "user:🙂"),
        rel.must_from_triple("doc:dé", "read", "user:u1"),
    ],
    # types the interner does not know, one a name that is no str
    "unknown-type": [
        rel.must_from_triple("ghost:x", "read", "user:u1"),
        rel.must_from_triple("doc:d1", "read", "ghost:y"),
        dataclasses.replace(MIXED[0], resource_type=7),
        dataclasses.replace(MIXED[0], subject_type=("user",)),
        rel.must_from_triple("doc:d3", "read", "user:u3"),
    ],
    # a relation with no slot on the resource's side, the subject's, both
    "no-slot": [
        rel.must_from_triple("doc:d1", "nosuch", "user:u1"),
        rel.must_from_triple("doc:d2", "read", "group:g0#nosuch"),
        rel.must_from_triple("group:g0", "nosuch", "group:g1#nosuch"),
        rel.must_from_triple("doc:d3", "read", "user:u3"),
    ],
    "decoded": [_decoded(r) for r in MIXED],
    "subclass": [TaggedRelationship(**{k: getattr(r, k) for k in KEY_FIELDS})
                 for r in MIXED],
    "slots": [SlotRelationship(*(getattr(r, k) for k in KEY_FIELDS))
              for r in MIXED],
    "mixed-objects": [
        f(r) for r in MIXED for f in (
            lambda r: r, _decoded,
            lambda r: SlotRelationship(*(getattr(r, k) for k in KEY_FIELDS)))],
}


def _native_batches():
    return metrics.default.counter("engine.lower_native_batches")


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("kind", INTERNERS)
def test_lowered_columns_equal_a_per_key_reference(kind, batch, lowering):
    engine, snap = _world(kind)
    rels = BATCHES[batch]
    before = _native_batches()
    queries, _ = engine._lower_queries(snap, rels)
    assert _native_batches() - before == (1 if lowering else 0)
    want = _reference_columns(engine, snap, rels)
    assert set(queries) == set(want) | {"q_ctx"}
    for k, col in want.items():
        assert queries[k].tolist() == col, k
        assert queries[k].dtype == (bool if k == "q_self" else np.int32), k
    assert queries["q_ctx"].tolist() == [-1] * len(rels)


def test_the_native_pull_engages_where_its_library_builds():
    """The pull is what the native interner's batches take on a host
    with a compiler and the interpreter's headers, as this one has."""
    assert native.available()
    assert native.lower_lib() is not None


def _bad(**fields):
    return [*MIXED[:3], dataclasses.replace(MIXED[3], **fields), *MIXED[4:6]]


BAD_BATCHES = {
    "non-str-resource-id": (_bad(resource_id=5), TypeError),
    "non-str-subject-id": (_bad(subject_id=None), TypeError),
    "surrogate-id": (_bad(subject_id="u\ud800"), UnicodeEncodeError),
    "unhashable-resource-type": (_bad(resource_type=["doc"]), TypeError),
    "unhashable-subject-type": (_bad(subject_type={}), TypeError),
    "unhashable-resource-relation": (_bad(resource_relation=["read"]), TypeError),
    "unhashable-subject-relation": (_bad(subject_relation=["member"]), TypeError),
    "missing-field": (
        [*MIXED[:2], SimpleNamespace(**{
            k: "x" for k in KEY_FIELDS if k != "subject_relation"})],
        AttributeError),
}


@pytest.mark.skipif(not native.available(), reason="no native interner")
@pytest.mark.parametrize("case", list(BAD_BATCHES))
def test_a_bad_field_raises_the_same_type_through_both_passes(
        case, monkeypatch, tmp_path):
    rels, error = BAD_BATCHES[case]
    engine, snap = _world("native")
    with pytest.raises(error):
        engine._lower_queries(snap, rels)
    _no_pull(monkeypatch, tmp_path)
    with pytest.raises(error):
        engine._lower_queries(snap, rels)


def _random_batch(n, seed):
    rng = np.random.default_rng(seed)
    pool = [r for b in BATCHES.values() for r in b]
    return [pool[i] for i in rng.integers(0, len(pool), n).tolist()]


@pytest.mark.skipif(not native.available(), reason="no native interner")
@pytest.mark.parametrize("how", ["disabled", "missing", "failed-build"])
def test_without_the_pull_the_python_pass_gives_the_same_columns(
        how, monkeypatch, tmp_path):
    """``native.set_enabled(False)``, a missing source or a build that
    fails leaves the engine on its Python pass, with the same columns as
    the native pull's; without the pull library the interner's library
    stays loaded."""
    engine, snap = _world("native")
    rels = _random_batch(2_000, 39)
    before = _native_batches()
    want, _ = engine._lower_queries(snap, rels)
    assert _native_batches() - before == 1
    if how == "disabled":
        monkeypatch.setattr(native, "_forced_off", native._forced_off)
        native.set_enabled(False)
    else:
        _no_pull(monkeypatch, tmp_path,
                 "#error a broken build\n" if how == "failed-build" else None)
        assert native.available()
        assert not (tmp_path / "libgochugaru_lower.so").exists()
    assert native.lower_lib() is None
    got, _ = engine._lower_queries(snap, rels)
    assert _native_batches() - before == 1
    for k, col in want.items():
        assert col.dtype == got[k].dtype and np.array_equal(col, got[k]), k


CAVEAT_SCHEMA = """
caveat same_tenant(tenant string, edge_tenant string, tier int) {
    tenant == edge_tenant && tier >= 1
}
definition user {}
definition doc {
  relation holder: user with same_tenant
  permission view = holder
}
"""


def _caveat_world():
    """Holders under three tenants, on the native interner; returns the
    engine and its prepared snapshot."""
    from gochugaru_tpu.native.interner import NativeInterner

    cs = compile_schema(parse_schema(CAVEAT_SCHEMA))
    stored = [rel.must_from_triple(f"doc:d{i}", "holder", f"user:u{i % 5}")
              .with_caveat("same_tenant", {"edge_tenant": f"t{i % 3}", "tier": 2})
              for i in range(20)]
    snap = build_snapshot(1, cs, NativeInterner(), stored,
                          epoch_us=1_700_000_000_000_000)
    engine = DeviceEngine(cs)
    return engine, engine.prepare(snap)


def _caveat_checks(n, seed):
    """Checks carrying ``{tenant, tier}`` as the caveat cell sends them, a
    sixth of them empty, some with an unknown tenant."""
    rng = np.random.default_rng(seed)
    sent = {k: {"tenant": f"t{k}", "tier": 2} for k in range(5)}
    out = []
    for d, u, k in zip(*(rng.integers(0, m, n).tolist() for m in (25, 6, 6))):
        r = rel.must_from_triple(f"doc:d{d}", "view", f"user:u{u}")
        out.append(r.with_caveat("", sent[k]) if k < 5 else r)
    return out


def _context_batches():
    return metrics.default.counter("engine.context_native_batches")


@pytest.mark.skipif(not native.available(), reason="no native interner")
@pytest.mark.parametrize("how", ["disabled", "missing", "failed-build"])
def test_without_the_library_the_contexts_group_in_python(
        how, monkeypatch, tmp_path):
    """The native grouping and the Python pass that runs without the
    lowering library give one ``q_ctx`` and one context table; only the
    first moves ``engine.context_native_batches``."""
    engine, dsnap = _caveat_world()
    rels = _caveat_checks(2_000, 43)
    before = _context_batches()
    want, want_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    assert _context_batches() - before == 1
    assert (want["q_ctx"] < 0).any() and want["q_ctx"].max() == 4
    if how == "disabled":
        monkeypatch.setattr(native, "_forced_off", native._forced_off)
        native.set_enabled(False)
    else:
        _no_pull(monkeypatch, tmp_path,
                 "#error a broken build\n" if how == "failed-build" else None)
    assert native_lower.contexts(rels, engine.caveat_plan.slots_of_param) is None
    got, got_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    assert _context_batches() - before == 1
    assert got["q_ctx"].tolist() == want["q_ctx"].tolist()
    for name in ("vi", "vf", "pr", "host"):
        assert np.array_equal(got_t[name], want_t[name]), name


@pytest.mark.skipif(not native.available(), reason="no native interner")
def test_the_native_grouping_answers_as_the_python_pass():
    """``check_batch`` over a caveat world: the planes of the native
    grouping equal those of the Python pass, batch for batch."""
    engine, dsnap = _caveat_world()
    rels = _caveat_checks(600, 44)
    before = _context_batches()
    got = [np.asarray(a) for a in engine.check_batch(dsnap, rels, now_us=NOW_US)]
    assert _context_batches() - before == (
        1 if native.lower_lib() is not None else 0)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(native, "_forced_off", True)
        want = [np.asarray(a) for a in engine.check_batch(dsnap, rels, now_us=NOW_US)]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert got[0].any() and not got[0].all()


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
def test_concurrent_lowering_with_a_writer_equals_one_thread(kind, lowering):
    """Four threads lower different batches at once while a fifth interns
    new nodes and new types (the native table grows under them): each
    gets the columns one thread gets."""
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
    before = metrics.default.counter("engine.lower_native_batches")
    got = [[] for _ in batches]
    errors = []
    stop, grown = threading.Event(), threading.Event()

    def lower(k):
        # six batches at least, and on until the writer has crossed the
        # rehash: a fast pass must not finish before the table grows
        try:
            for n in range(1_000):
                got[k].append(engine._lower_queries(snap, batches[k])[0])
                if n >= 5 and grown.is_set():
                    break
        except Exception as e:  # surfaced below
            errors.append(e)

    def write():
        i = 0
        while not stop.is_set() and i < 120_000:
            # a type no batch names, now and then: the type table grows too
            snap.interner.node(f"newtype{i}" if i % 64 == 0 else "user",
                               f"new{i}")
            i += 1
            if i == 1_000:
                grown.set()

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
    assert snap.interner.type_lookup("newtype320") >= 0
    lowered = sum(map(len, got))
    assert metrics.default.counter("engine.lower_native_batches") - before == (
        lowered if lowering else 0)
    for k, runs in enumerate(got):
        assert len(runs) >= 6
        for q in runs:
            for name, col in want[k].items():
                assert np.array_equal(q[name], col), (k, name)
