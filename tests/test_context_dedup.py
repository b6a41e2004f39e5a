"""The request-context dedup of a check batch (``caveats.device.
dedup_contexts``, called by ``DeviceEngine._lower``): contexts grouped a
parameter column at a time must encode, check for check, as the per-row key
``repr(sorted(context.items()))`` encodes them — the key the lowering used
before, kept here as the reference."""

from typing import Dict, List

import numpy as np
import pytest

from gochugaru_tpu import rel
from gochugaru_tpu.caveats import compile_cel
from gochugaru_tpu.caveats.cel import CelCompileError
from gochugaru_tpu.caveats.device import build_caveat_plan, dedup_contexts
from gochugaru_tpu.engine import device as engine_device
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.oracle import F, Oracle, T
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


@pytest.fixture(scope="module")
def world():
    cs = compile_schema(parse_schema(SCHEMA))
    stored = [rel.must_from_triple(doc, "viewer", "user:u1").with_caveat(cav, ctx)
              for doc, cav, ctx in STORED]
    snap = build_snapshot(1, cs, Interner(), stored, epoch_us=NOW)
    progs = {name: compile_cel(name, decl.params, decl.expression)
             for name, decl in cs.schema.caveats.items()}
    engine = DeviceEngine(cs)
    return engine, engine.prepare(snap), Oracle(cs, stored, progs, now_us=NOW)


def checks():
    return [rel.must_from_triple(doc, "view", "user:u1").with_caveat("", c)
            for doc, _, _ in STORED for c in CONTEXTS]


def with_dedup(monkeypatch, dedup, call):
    """``call()`` with ``dedup`` as the engine's context dedup."""
    with monkeypatch.context() as m:
        m.setattr(engine_device, "dedup_contexts", dedup)
        return call()


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
