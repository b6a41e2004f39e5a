"""A lookup's granted nodes become id strings a BLOCK at a time
(engine/spmv.py ``_ResultStream`` → ``interner.keys_columns``), never
one ``key_of`` an id: with the native interner each ``key_of`` is a
foreign call that lets go of the interpreter lock, and four callers
turned that into a hand-over an id (PERF.md §6, PR 34).

Held here: both interners decode a block exactly as ``key_of`` would;
no ``key_of`` runs on the granted path; pages cut inside a block resume
exactly; concurrent callers each get their own answer; and the
``lookup.ids`` / ``lookup.id_blocks`` counters count what was decoded."""

import sys
import threading

import numpy as np
import pytest

from gochugaru_tpu import native, rel
from gochugaru_tpu.engine import lookup as lm
from gochugaru_tpu.engine import spmv
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.oracle import Oracle
from gochugaru_tpu.native.interner import NativeInterner
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils.metrics import default as m

NOW = 1_700_000_000_000_000

INTERNERS = {"python": Interner, "native": NativeInterner}


@pytest.fixture(params=sorted(INTERNERS))
def interner(request):
    if request.param == "native" and not native.available():
        pytest.skip("the native library does not load here")
    return INTERNERS[request.param]()


# ---------------------------------------------------------------------------
# (a) the block decode of both interners
# ---------------------------------------------------------------------------

ASCII = [("document", f"d{i}") for i in range(40)] + [
    ("user", "u:with#marks"), ("group", ""), ("user", "*"),
    ("document", "x" * 300),  # longer than key_of's first buffer
]
NON_ASCII = ASCII[:5] + [("user", "zoë"), ("document", "文書-7"),
                         ("user", "u1")]


@pytest.mark.parametrize("keys", [ASCII, NON_ASCII, []],
                         ids=["ascii", "non_ascii", "empty"])
def test_keys_columns_equals_key_of(interner, keys):
    nodes = [interner.node(t, i) for t, i in keys]
    # a block in its own order: reversed, with a repeat
    block = np.asarray(nodes[::-1] + nodes[:2], np.int64)
    types, ids = interner.keys_columns(block)
    want = [interner.key_of(int(n)) for n in block]
    assert types == [t for t, _ in want]
    assert ids == [i for _, i in want]
    assert isinstance(types, list) and isinstance(ids, list)


@pytest.mark.parametrize("bad", [-1, 3, 1 << 40])
def test_keys_columns_unknown_node_raises(interner, bad):
    nodes = [interner.node("user", f"u{i}") for i in range(3)]
    with pytest.raises(IndexError):
        interner.keys_columns(np.asarray(nodes + [bad], np.int64))


# ---------------------------------------------------------------------------
# a small nested-groups world (the benchmark's docs schema)
# ---------------------------------------------------------------------------

DOCS = """
definition user {}
definition group { relation member: user | group#member }
definition folder {
    relation parent: folder
    relation viewer: user | group#member
    permission view = viewer + parent->view
}
definition document {
    relation folder: folder
    relation viewer: user | group#member
    permission view = viewer + folder->view
}
"""

N_USERS, N_GROUPS, N_FOLDERS, N_DOCS = 24, 6, 7, 120


def docs_rels():
    rng = np.random.default_rng(11)
    t = rel.must_from_tuple
    rels = []
    # groups nest in one chain g0 ⊂ g1 ⊂ … (g_{k+1} holds g_k's members)
    for g in range(N_GROUPS):
        if g:
            rels.append(t(f"group:g{g}#member", f"group:g{g - 1}#member"))
        for u in rng.choice(N_USERS, 3, replace=False):
            rels.append(t(f"group:g{g}#member", f"user:u{u}"))
    # a binary folder tree; the root is viewed by the outermost group
    for f in range(1, N_FOLDERS):
        rels.append(t(f"folder:f{f}#parent", f"folder:f{(f - 1) // 2}"))
    rels.append(t("folder:f0#viewer", f"group:g{N_GROUPS - 1}#member"))
    rels.append(t("folder:f3#viewer", "user:u1"))
    for d in range(N_DOCS):
        rels.append(t(f"document:d{d}#folder", f"folder:f{d % N_FOLDERS}"))
        rels.append(t(f"document:d{d}#viewer",
                      f"group:g{rng.integers(N_GROUPS)}#member"))
        rels.append(t(f"document:d{d}#viewer",
                      f"user:u{rng.integers(N_USERS)}"))
    return list(dict.fromkeys(rels))


@pytest.fixture
def docs(interner):
    rels = docs_rels()
    cs = compile_schema(parse_schema(DOCS))
    snap = build_snapshot(1, cs, interner, rels, epoch_us=NOW)
    oracle = Oracle(cs, rels, {}, now_us=NOW)
    engine = DeviceEngine(cs)
    return engine, engine.prepare(snap), oracle, interner


@pytest.fixture
def yielded(monkeypatch):
    """Sizes of the candidate blocks every stream built from here on
    pulls from its iterator."""
    sizes = []

    class Counting(spmv._ResultStream):
        def __init__(self, cand_iter, *a, **kw):
            def counted():
                for block in cand_iter:
                    sizes.append(int(block.size))
                    yield block
            super().__init__(counted(), *a, **kw)

    monkeypatch.setattr(spmv, "_ResultStream", Counting)
    return sizes


def _resources(docs, uid):
    engine, dsnap, oracle, _ = docs
    return lm.lookup_resources_device(
        engine, dsnap, "document", "view", "user", uid,
        now_us=NOW, oracle_factory=lambda: oracle)


def _subjects(docs, did):
    engine, dsnap, oracle, _ = docs
    return lm.lookup_subjects_device(
        engine, dsnap, "document", did, "view", "user",
        now_us=NOW, oracle_factory=lambda: oracle)


# ---------------------------------------------------------------------------
# (b) no key_of an id on the granted path
# ---------------------------------------------------------------------------


def test_drained_lookups_never_call_key_of(docs, monkeypatch):
    engine, dsnap, oracle, interner = docs
    calls = []
    real = interner.key_of
    monkeypatch.setattr(
        interner, "key_of", lambda n: (calls.append(n), real(n))[1])
    total = 0
    for u in range(N_USERS):
        got = _resources(docs, f"u{u}")
        assert got == sorted(oracle.lookup_resources(
            "document", "view", "user", f"u{u}", ""))
        total += len(got)
    for d in range(0, N_DOCS, 9):
        got = _subjects(docs, f"d{d}")
        assert got == sorted(oracle.lookup_subjects(
            "document", f"d{d}", "view", "user", ""))
        total += len(got)
    assert total > 500, "the world must grant something to decode"
    assert calls == []


# ---------------------------------------------------------------------------
# (c) pages cut inside a candidate block
# ---------------------------------------------------------------------------


def _pages(page_fn, page_size, cursor=None):
    """[(ids, cursor), …] from ``cursor`` to the end of the stream."""
    out = []
    while True:
        ids, cursor = page_fn(page_size=page_size, cursor=cursor)
        out.append((ids, cursor))
        if cursor is None:
            return out


@pytest.mark.parametrize("surface", ["resources", "subjects"])
def test_pages_smaller_than_a_block_resume_exactly(docs, surface, yielded):
    engine, dsnap, oracle, _ = docs
    if surface == "resources":
        # u1 views f3 and, through the nesting, much of the world
        full = _resources(docs, "u1")

        def page_fn(**kw):
            return lm.lookup_resources_page(
                engine, dsnap, "document", "view", "user", "u1",
                now_us=NOW, oracle_factory=lambda: oracle, **kw)
    else:
        full = _subjects(docs, "d0")

        def page_fn(**kw):
            return lm.lookup_subjects_page(
                engine, dsnap, "document", "d0", "view", "user",
                now_us=NOW, oracle_factory=lambda: oracle, **kw)

    (drained, end), = _pages(page_fn, 1 << 20)
    assert end is None and sorted(drained) == full
    assert len(drained) == len(set(drained))
    page_size = 5
    # every candidate of this world is granted, so a candidate block is a
    # decoded block
    assert max(yielded) > 2 * page_size, "a block must span several pages"

    # live continuation: each page resumes the cached stream
    dsnap.__dict__.pop("_lookup_streams", None)
    pages = _pages(page_fn, page_size)
    assert [i for ids, _ in pages for i in ids] == drained
    assert all(len(ids) == page_size for ids, _ in pages[:-1])
    # from EVERY cursor, with the stream cache cleared: recompute-and-skip
    base = m.counter("lookup.stream_recomputes")
    at = 0
    for ids, cursor in pages[:-1]:
        at += len(ids)
        dsnap.__dict__.pop("_lookup_streams", None)
        rest = _pages(page_fn, page_size, cursor)
        assert [i for p, _ in rest for i in p] == drained[at:]
    assert m.counter("lookup.stream_recomputes") - base == len(pages) - 1


def test_result_stream_keeps_block_order_across_takes():
    """The stream alone: granted blocks decode whole, in their own
    order; ``take`` / ``skip`` cut anywhere inside them."""
    blocks = [np.array([5, 3, 9]), np.empty(0, np.int64), np.array([4]),
              np.array([8, 6]), np.array([7, 1, 2, 0])]
    decoded = []

    def ids_of(nodes):
        decoded.append(nodes.tolist())
        return [f"n{n}" for n in nodes.tolist()]

    def make():
        # the filter drops even nodes: block [8, 6] grants nothing
        return spmv._ResultStream(
            iter(blocks), lambda b: b[b % 2 == 1], ids_of)

    want = ["n5", "n3", "n9", "n7", "n1"]
    s = make()
    assert s.take(100) == want and s.exhausted and s.emitted == 5
    assert decoded == [[5, 3, 9], [7, 1]], "one call a granted block"
    s = make()
    assert [s.take(2), s.take(2), s.take(2), s.take(2)] == [
        want[:2], want[2:4], want[4:], []]
    s = make()
    s.skip(3)
    assert s.emitted == 3 and s.take(9) == want[3:]


# ---------------------------------------------------------------------------
# (d) concurrent callers on one client
# ---------------------------------------------------------------------------


def test_four_threads_drain_their_own_lookups():
    from gochugaru_tpu import consistency, new_tpu_evaluator
    from gochugaru_tpu.rel.txn import Txn
    from gochugaru_tpu.utils import background

    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, DOCS)
    txn = Txn()
    for r in docs_rels():
        txn.create(r)
    cs = consistency.at_least(c.write(ctx, txn))
    calls = [
        lambda: list(c.lookup_resources(ctx, cs, "document#view", "user:u1")),
        lambda: list(c.lookup_resources(ctx, cs, "document#view", "user:u7")),
        lambda: list(c.lookup_subjects(ctx, cs, "document:d0", "view", "user")),
        lambda: list(c.lookup_subjects(ctx, cs, "document:d5", "view", "user")),
    ]
    alone = [call() for call in calls]
    assert all(alone) and len({tuple(a) for a in alone}) == 4
    rounds = 6
    got = [[] for _ in calls]
    errors = []
    gate = threading.Barrier(len(calls))

    def run(k):
        try:
            gate.wait(timeout=30)
            for _ in range(rounds):
                got[k].append(calls[k]())
        except BaseException as e:  # reported below, on the test's thread
            errors.append(e)
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(k,), daemon=True)
                   for k in range(len(calls))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    for k, a in enumerate(alone):
        assert got[k] == [a] * rounds


# ---------------------------------------------------------------------------
# (e) the counters the block decode brings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("surface", ["resources", "subjects"])
def test_id_counters_count_the_decoded_blocks(docs, surface, yielded):
    ids0, blocks0 = m.counter("lookup.ids"), m.counter("lookup.id_blocks")
    got = (_resources(docs, "u1") if surface == "resources"
           else _subjects(docs, "d0"))
    assert got and yielded
    assert m.counter("lookup.ids") - ids0 == len(got)
    assert 1 <= m.counter("lookup.id_blocks") - blocks0 <= len(yielded)
