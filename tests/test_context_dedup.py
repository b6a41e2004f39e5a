"""The request-context dedup of a check batch (``caveats.device.
dedup_contexts``, called by ``DeviceEngine._lower``): contexts grouped a
parameter column at a time must encode, check for check, as the per-row key
``repr(sorted(context.items()))`` encodes them — the key the lowering used
before, kept here as the reference.  The native grouping of the lowering
(``native.lower.contexts``, taken where the native pull lowered the batch)
must give ``dedup_contexts``' groups, or decline the batch to it.

The tests that swap ``dedup_contexts`` run on a world of the pure-Python
interner, whose batches the native pull never lowers, so the swap holds."""

import types
from typing import Dict, List

import numpy as np
import pytest

from gochugaru_tpu import native, rel
from gochugaru_tpu.caveats import compile_cel
from gochugaru_tpu.caveats.cel import CelCompileError
from gochugaru_tpu.caveats.device import build_caveat_plan, dedup_contexts
from gochugaru_tpu.engine import device as engine_device
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.oracle import F, Oracle, T
from gochugaru_tpu.native import lower as native_lower
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import metrics

NOW = 1_700_000_000_000_000
SCHEMA = """
caveat at_least(tier int, minimum int) { tier >= minimum }
caveat ratio_ok(ratio double) { ratio < 2.5 }
caveat from_ip(ip string) { ip == '10.0.0.1' }
caveat flagged(on bool) { on }
caveat listed(ip string, allowed list<string>) { ip in allowed }
definition user {}
definition doc {
    relation viewer: user with at_least | user with ratio_ok | user with from_ip | user with flagged | user with listed
    permission view = viewer
}
"""
STORED = [
    ("doc:a", "at_least", {"minimum": 1}),
    ("doc:b", "ratio_ok", {}),
    ("doc:c", "from_ip", {}),
    ("doc:d", "flagged", {}),
    ("doc:e", "listed", {"allowed": ["10.0.0.1"]}),
]
#: the batch's request contexts: equal ones as other dicts in another key
#: order, 1 / True / 1.0 / "1" in one parameter, a parameter missing and an
#: explicit None, an undeclared key, an empty context, one of undeclared
#: keys alone, a list value, unknown strings, -0.0 beside 0.0
CONTEXTS = [
    {"tier": 1, "ratio": 1.0, "ip": "10.0.0.1"},
    {"ip": "10.0.0.1", "ratio": 1.0, "tier": 1},
    {"tier": 1},
    {"tier": True},
    {"tier": 1.0},
    {"tier": "1"},
    {"tier": 1, "colour": "red"},
    {"ratio": 2.0},
    {"tier": None},
    {"tier": None, "on": None},
    {},
    {"colour": "blue"},
    {"colour": "green"},
    {"ip": "10.0.0.1", "allowed": ["10.0.0.1"]},
    {"ip": "10.0.0.1", "allowed": ["10.0.0.2"]},
    {"ip": "8.8.8.8"},
    {"ip": "9.9.9.9", "on": True},
    {"ip": "8.8.8.8", "on": 1},
    {"ip": "8.8.8.8", "on": None},
    {"on": True},
    {"on": 1},
    {"ratio": -0.0},
    {"ratio": 0.0},
    {"on": False, "tier": 0},
    {"on": 0, "tier": False},
]
#: parameter columns the batch names: by value ip and on (str / bool and
#: int / None), by repr tier (it holds a float), ratio and allowed
KEYED, BY_REPR = 2, 3


def reference_dedup(plan, contexts):
    """The per-row key: one ``repr(sorted(items))`` a context."""
    index: Dict[str, int] = {}
    rows: List[dict] = []
    at = []
    for c in contexts:
        key = repr(sorted(c.items(), key=lambda kv: kv[0]))
        if key not in index:
            index[key] = len(rows)
            rows.append(c)
        at.append(index[key])
    return np.asarray(at, np.int32), rows, 0, 0


def _build_world(interner):
    cs = compile_schema(parse_schema(SCHEMA))
    stored = [rel.must_from_triple(doc, "viewer", "user:u1").with_caveat(cav, ctx)
              for doc, cav, ctx in STORED]
    snap = build_snapshot(1, cs, interner, stored, epoch_us=NOW)
    progs = {name: compile_cel(name, decl.params, decl.expression)
             for name, decl in cs.schema.caveats.items()}
    engine = DeviceEngine(cs)
    return engine, engine.prepare(snap), Oracle(cs, stored, progs, now_us=NOW)


@pytest.fixture(scope="module")
def world():
    return _build_world(Interner())


@pytest.fixture(scope="module")
def native_world():
    """The world on the native interner: its batches take the native pull,
    and with it the native grouping of their contexts."""
    from gochugaru_tpu.native.interner import NativeInterner

    return _build_world(NativeInterner())


def checks():
    return [rel.must_from_triple(doc, "view", "user:u1").with_caveat("", c)
            for doc, _, _ in STORED for c in CONTEXTS]


def with_dedup(monkeypatch, dedup, call):
    """``call()`` with ``dedup`` as the engine's context dedup, which the
    call must reach (the native grouping would go round it)."""
    calls = []

    def counted(plan, contexts):
        calls.append(len(contexts))
        return dedup(plan, contexts)

    with monkeypatch.context() as m:
        m.setattr(engine_device, "dedup_contexts", counted)
        out = call()
    assert calls, "the lowering did not reach the swapped dedup"
    return out


def lowered(monkeypatch, engine, dsnap, rels, dedup):
    """``(q_ctx, qctx)`` of one lowering with ``dedup`` in the engine."""
    q, qctx = with_dedup(monkeypatch, dedup, lambda: engine._lower_queries(
        dsnap.snapshot, rels, dsnap.strings))
    return q["q_ctx"], qctx


def test_every_check_encodes_as_the_per_row_key_encodes_it(world, monkeypatch):
    engine, dsnap, _ = world
    rels = checks()
    got, got_t = lowered(monkeypatch, engine, dsnap, rels, dedup_contexts)
    want, want_t = lowered(monkeypatch, engine, dsnap, rels, reference_dedup)
    assert ((got < 0) == (want < 0)).all()
    assert (got < 0).sum() == len(STORED)  # the empty contexts, and only they
    rows = got.max() + 1
    assert rows <= want.max() + 1
    has = got >= 0
    for name in ("vi", "vf", "pr", "host"):
        a, b = got_t[name][got[has]], want_t[name][want[has]]
        assert np.array_equal(a, b), name
        if name == "vf":  # -0.0 and 0.0 stay apart, bit for bit
            assert np.array_equal(a.view(np.int32), b.view(np.int32))


def test_the_groups_are_the_reference_groups_less_the_undeclared_keys(world):
    engine, dsnap, _ = world
    plan = engine.caveat_plan
    given = [c for c in CONTEXTS if c]
    index, rows, keyed, by_repr = dedup_contexts(plan, given)
    declared = [{k: v for k, v in c.items() if k in plan.slots_of_param}
                for c in given]
    want, _, _, _ = reference_dedup(plan, declared)
    # the same partition, numbered in the order groups first come
    assert index.tolist() == want.tolist()
    assert all(given[index.tolist().index(g)] is r for g, r in enumerate(rows))
    assert (keyed, by_repr) == (KEYED, BY_REPR)
    # {"tier": 1, "colour": "red"} is {"tier": 1}; the two of undeclared
    # keys alone are one row of nothing
    assert index[given.index(CONTEXTS[6])] == index[given.index(CONTEXTS[2])]
    assert index[given.index(CONTEXTS[11])] == index[given.index(CONTEXTS[12])]
    assert len(rows) < len(reference_dedup(plan, given)[1])


def test_the_counters_of_a_batch(world, monkeypatch):
    engine, dsnap, _ = world
    names = ("engine.context_batches", "engine.context_checks",
             "engine.context_keyed_columns", "engine.context_repr_columns")
    before = [metrics.default.counter(k) for k in names]
    rels = checks()
    lowered(monkeypatch, engine, dsnap, rels, dedup_contexts)
    gained = [metrics.default.counter(k) - b for k, b in zip(names, before)]
    assert gained == [1, len(rels) - len(STORED), KEYED, BY_REPR]


def oracle_answer(oracle, r):
    """The oracle's tri-state, or None where it refuses the context (a
    string where the caveat compares an int)."""
    try:
        return oracle.check_relationship(r)
    except CelCompileError:
        return None


def test_the_verdicts_are_the_oracles(world, monkeypatch):
    engine, dsnap, oracle = world
    rels = checks()
    planes = {name: [np.asarray(a) for a in with_dedup(
        monkeypatch, dedup, lambda: engine.check_batch(dsnap, rels, now_us=NOW))]
        for name, dedup in (("columns", dedup_contexts), ("per-row", reference_dedup))}
    for a, b in zip(planes["columns"], planes["per-row"]):
        assert np.array_equal(a, b)
    definite, possible, _ = planes["columns"]
    answered = 0
    for i, r in enumerate(rels):
        want = oracle_answer(oracle, r)
        if want is None:
            continue
        answered += 1
        assert not definite[i] or want == T, r
        assert possible[i] or want == F, r
    assert answered > len(rels) // 2
    assert definite.any() and not definite.all()


def _random_contexts(seed: int, n: int):
    rng = np.random.default_rng(seed)
    pools = {
        "tier": [0, 1, 2, True, False, None, 1.0, "1"],
        "ip": ["10.0.0.1", "10.0.0.2", "8.8.8.8", None, 7],
        "on": [True, False, 0, 1, None],
        "ratio": [0.5, 2.0, -0.0, 0.0, 1, None],
        "colour": ["red", "blue"],
    }
    out = []
    for _ in range(n):
        c = {}
        for k in rng.permutation(list(pools)).tolist():
            if rng.random() < 0.7:
                vals = pools[k]
                c[k] = vals[int(rng.integers(0, len(vals)))]
        out.append(c or {"colour": "red"})
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_contexts_group_as_their_declared_parameters(seed):
    plan = build_caveat_plan(compile_schema(parse_schema(SCHEMA)))
    given = _random_contexts(seed, 3000)
    index, rows, _, _ = dedup_contexts(plan, given)
    declared = [{k: v for k, v in c.items() if k in plan.slots_of_param}
                for c in given]
    want, _, _, _ = reference_dedup(plan, declared)
    assert index.tolist() == want.tolist()
    assert len(rows) == want.max() + 1


def test_wide_keys_are_compacted_and_stay_exact():
    """Three columns of 3,000 distinct ints each: 2.7e10 combined values,
    over the dense table's room, so the key is renumbered between columns."""
    plan = build_caveat_plan(compile_schema(parse_schema("""
        caveat c(a int, b int, d int) { a + b + d > 0 }
        definition user {}
        definition doc { relation viewer: user with c }
    """)))
    rng = np.random.default_rng(7)
    n = 3000
    cols = [rng.permutation(n) for _ in range(3)]
    given = [{"a": int(a), "b": int(b), "d": int(d)} for a, b, d in zip(*cols)]
    given += given[: n // 2]  # every second half row again
    index, rows, keyed, by_repr = dedup_contexts(plan, given)
    assert (keyed, by_repr) == (3, 0)
    assert len(rows) == n
    assert index.tolist() == list(range(n)) + list(range(n // 2))


# -- the native grouping ----------------------------------------------------

needs_lower = pytest.mark.skipif(
    native.lower_lib() is None, reason="no native lowering library")
PARAMS = tuple(build_caveat_plan(compile_schema(parse_schema(SCHEMA))).slots_of_param)


def _python_pass(plan, contexts):
    """``DeviceEngine._lower``'s Python pass over the request contexts:
    ``(index, rows, keyed)``, -1 where a context is empty."""
    index = np.full(len(contexts), -1, np.int32)
    at = [i for i, c in enumerate(contexts) if c]
    if not at:
        return index, [], 0
    index[at], rows, keyed, _ = dedup_contexts(plan, [contexts[i] for i in at])
    return index, rows, keyed


def _holders(contexts):
    """Objects whose ``caveat_context`` is each context itself (a
    ``Relationship`` would copy it)."""
    return [types.SimpleNamespace(caveat_context=c) for c in contexts]


def _assert_native_is_python(contexts):
    plan = build_caveat_plan(compile_schema(parse_schema(SCHEMA)))
    got = native_lower.contexts(_holders(contexts), plan.slots_of_param)
    assert got is not None
    index, rows, keyed = got
    want_index, want_rows, want_keyed = _python_pass(plan, contexts)
    assert index.dtype == np.int32
    assert index.tolist() == want_index.tolist()
    assert len(rows) == len(want_rows)
    assert all(a is b for a, b in zip(rows, want_rows))
    assert keyed == want_keyed
    return index, rows, keyed


def _exact_contexts(seed: int, n: int):
    """Random contexts of the values the native pass keys: str, int,
    bool, None, a parameter missing, empty contexts, undeclared keys."""
    rng = np.random.default_rng(seed)
    pools = {
        "tier": [0, 1, 2, True, False, None, "1", 2**70, -1, -2],
        "ip": ["10.0.0.1", "10.0.0.2", "8.8.8.8", None, 7, ""],
        "on": [True, False, 0, 1, None],
        "allowed": ["a", "b", 1],
        "colour": ["red", "blue"],
    }
    out = []
    for _ in range(n):
        c = {}
        for k in rng.permutation(list(pools)).tolist():
            if rng.random() < 0.5:
                vals = pools[k]
                c[k] = vals[int(rng.integers(0, len(vals)))]
        out.append(c)
    return out


@needs_lower
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_native_grouping_is_dedup_contexts(seed):
    contexts = _exact_contexts(seed, 3000)
    index, rows, _ = _assert_native_is_python(contexts)
    assert (index < 0).tolist() == [not c for c in contexts]
    assert len(rows) > 10


#: value mixes the native pass keys exactly as ``dedup_contexts`` does
MIXES = {
    "every-kind": [{"tier": 1}, {"tier": "1"}, {"tier": None}, {},
                   {"ip": "x"}, {"tier": False}, {"tier": 1, "ip": None}],
    "one-beside-true": [{"tier": 1}, {"tier": True}, {"tier": 1},
                        {"tier": True}, {"on": 0}, {"on": False},
                        {"on": 1}, {"on": True}, {"on": 0}],
    "none-beside-missing": [{"tier": None}, {"ip": "a"}, {"tier": None, "ip": "a"},
                            {"colour": "red"}, {"tier": None}],
    # -1 and -2 share a hash; ints past 64 bits; equal ints, other objects
    "large-ints": [{"tier": -1}, {"tier": -2}, {"tier": 2**64},
                   {"tier": 2**64 + 1}, {"tier": int("1" * 40)},
                   {"tier": int("1" * 40)}, {"tier": -(2**70)}, {"tier": -1}],
    # equal strings as other objects, keys built at run time (not interned)
    "equal-other-objects": [{"ip": "".join(["10.", "0"])}, {"ip": "10.0"},
                            {"".join(["t", "ier"]): 3}, {"tier": 3},
                            {"ip": "é" * 3}, {"ip": "".join(["é"] * 3)}],
    "undeclared-only": [{"colour": "red"}, {"colour": "blue"}, {"size": 1}],
    "one-parameter-named": [{"on": True}, {"on": True}, {"colour": "x"}],
    "all-empty": [{}, {}, {}],
    "none": [],
}


@needs_lower
@pytest.mark.parametrize("mix", list(MIXES))
def test_value_mixes_group_as_dedup_contexts_groups_them(mix):
    _assert_native_is_python(MIXES[mix])


class _Str(str):
    pass


class _Int(int):
    pass


#: batches the native pass declines, and the Python pass groups
DECLINED = {
    "float": {"ratio": 1.0},
    "float-in-an-int-parameter": {"tier": 1.0},
    "str-subclass": {"ip": _Str("10.0.0.1")},
    "int-subclass": {"tier": _Int(1)},
    "list": {"allowed": ["10.0.0.1"]},
    "str-subclass-key": {_Str("tier"): 1},
    "non-dict-mapping": types.MappingProxyType({"tier": 1}),
}


def _declined_batch(case):
    """Each stored doc against four contexts, the third ``DECLINED[case]``
    as it is (a ``Relationship`` would copy it into a dict)."""
    contexts = [{"tier": 1, "ip": "10.0.0.1"}, {}, None, {"tier": 1}]
    rels = []
    for doc, _, _ in STORED:
        for c in contexts:
            r = rel.must_from_triple(doc, "view", "user:u1").with_caveat("", c or {})
            if c is None:
                object.__setattr__(r, "caveat_context", DECLINED[case])
            rels.append(r)
    return rels


@needs_lower
@pytest.mark.parametrize("case", list(DECLINED))
def test_a_declined_batch_takes_the_python_pass(case, native_world, monkeypatch):
    engine, dsnap, _ = native_world
    rels = _declined_batch(case)
    assert native_lower.contexts(rels, engine.caveat_plan.slots_of_param) is None
    names = ("engine.context_native_batches", "engine.context_batches",
             "engine.lower_native_batches")
    before = [metrics.default.counter(k) for k in names]
    got, got_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    gained = [metrics.default.counter(k) - b for k, b in zip(names, before)]
    assert gained == [0, 1, 1]
    monkeypatch.setattr(native, "_forced_off", True)
    want, want_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    assert got["q_ctx"].tolist() == want["q_ctx"].tolist()
    for name in ("vi", "vf", "pr", "host"):
        assert np.array_equal(got_t[name], want_t[name]), name


@needs_lower
def test_a_row_without_a_context_raises_attribute_error_on_both_passes(
        native_world, monkeypatch):
    engine, dsnap, _ = native_world
    fields = ("resource_type", "resource_id", "resource_relation",
              "subject_type", "subject_id", "subject_relation")
    good = _exact_checks()[:3]
    bare = types.SimpleNamespace(**{k: getattr(good[0], k) for k in fields})
    rels = [*good, bare, *good]
    with pytest.raises(AttributeError):
        native_lower.contexts(rels, PARAMS)
    with pytest.raises(AttributeError):
        engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    monkeypatch.setattr(native, "_forced_off", True)
    with pytest.raises(AttributeError):
        engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)


def _exact_checks():
    """``checks()`` less the contexts the native pass declines."""
    exact = [c for c in CONTEXTS
             if all(type(v) in (str, int, bool, type(None)) for v in c.values())]
    return [rel.must_from_triple(doc, "view", "user:u1").with_caveat("", c)
            for doc, _, _ in STORED for c in exact]


@needs_lower
def test_the_native_grouping_counts_once_a_batch(native_world, monkeypatch):
    engine, dsnap, _ = native_world
    rels = _exact_checks()
    names = ("engine.context_native_batches", "engine.context_batches",
             "engine.context_checks", "engine.context_keyed_columns",
             "engine.context_repr_columns", "engine.query_contexts")
    before = [metrics.default.counter(k) for k in names]
    got, got_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    native_gain = [metrics.default.counter(k) - b for k, b in zip(names, before)]
    assert native_gain[:2] == [1, 1]
    monkeypatch.setattr(native, "_forced_off", True)
    before = [metrics.default.counter(k) for k in names]
    want, want_t = engine._lower_queries(dsnap.snapshot, rels, dsnap.strings)
    python_gain = [metrics.default.counter(k) - b for k, b in zip(names, before)]
    assert python_gain[0] == 0
    assert native_gain[1:] == python_gain[1:]
    assert got["q_ctx"].tolist() == want["q_ctx"].tolist()
    for name in ("vi", "vf", "pr", "host"):
        assert np.array_equal(got_t[name], want_t[name]), name


@needs_lower
def test_the_verdicts_are_the_oracles_on_the_native_path(world, native_world):
    """The verdict test's batch, less the contexts the native pass
    declines, through the native grouping: the same planes as the world
    of the Python interner (the Python pass), and the oracle's answers."""
    rels = _exact_checks()
    engine, dsnap, oracle = native_world
    before = metrics.default.counter("engine.context_native_batches")
    planes = [np.asarray(a) for a in engine.check_batch(dsnap, rels, now_us=NOW)]
    assert metrics.default.counter("engine.context_native_batches") > before
    py_engine, py_dsnap, _ = world
    want = [np.asarray(a) for a in py_engine.check_batch(py_dsnap, rels, now_us=NOW)]
    for a, b in zip(planes, want):
        assert np.array_equal(a, b)
    definite, possible, _ = planes
    answered = 0
    for i, r in enumerate(rels):
        answer = oracle_answer(oracle, r)
        if answer is None:
            continue
        answered += 1
        assert not definite[i] or answer == T, r
        assert possible[i] or answer == F, r
    assert answered > len(rels) // 2
    assert definite.any() and not definite.all()
