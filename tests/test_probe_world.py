"""The probe chain on the suite's richest random world.

The world (caveats with and without context, query context, wildcards,
userset and permission-valued subjects, expirations, team chains deep
enough to spill a small closure cap) puts traffic on every probe site of
engine/flat.py.  The flat chain is held to the host oracle
(engine/oracle.py) under each table layout the engine can build —
including ``flat_packed + flat_aligned``, what a TPU resolves to and the
CPU default does not — the frontier lookups to the host walker and the
oracle, and the pinned latency tiers to the throughput path with no
retrace across query-context shapes.
"""

import dataclasses
import datetime as dt
import functools
import pathlib
import random
import re

import numpy as np
import pytest

import gochugaru_tpu
from gochugaru_tpu import rel
from gochugaru_tpu.caveats import compile_cel
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.lookup import (
    lookup_resources_device,
    lookup_subjects_device,
)
from gochugaru_tpu.engine.oracle import F, Oracle, SnapshotOracle, T
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import metrics

NOW = 1_700_000_000_000_000

SCHEMA = """
caveat on_tuesday(day string) { day == "tuesday" }
definition user {}
definition team {
    relation member: user | team#member | user:*
    permission everyone = member
}
definition doc {
    relation reader: user | user:* | team#member | team#everyone
    relation writer: user | team#member
    permission edit = writer
    permission view = reader + edit
}
"""

#: "default" is what EngineConfig.for_schema resolves in this process: on
#: the CPU that is the packed layout too, on a TPU the aligned one
LAYOUTS = {
    "default": {},
    "packed": {"flat_packed": True},
    "packed+aligned": {"flat_packed": True, "flat_aligned": True},
    "closure-cap4": {"closure_source_cap": 4},
}


def _random_world(seed: int, n_edges: int):
    """Direct / wildcard / userset subjects, caveats with and without
    context, expirations, team chains deep enough to overflow a small
    closure cap — every probe site gets traffic."""
    rng = random.Random(seed)
    n_docs = max(n_edges // 8, 8)
    n_users = max(n_edges // 16, 8)
    n_teams = 32
    rels = []
    for t in range(1, n_teams):
        parent = t - 1 if t % 7 else rng.randrange(t)
        rels.append(rel.Relationship(
            resource_type="team", resource_id=f"t{parent}",
            resource_relation="member",
            subject_type="team", subject_id=f"t{t}",
            subject_relation="member",
        ))
    for t in range(n_teams):
        rels.append(rel.Relationship(
            resource_type="team", resource_id=f"t{t}",
            resource_relation="member",
            subject_type="user", subject_id=f"u{rng.randrange(n_users)}",
        ))
    rels.append(rel.Relationship(
        resource_type="team", resource_id="t3", resource_relation="member",
        subject_type="user", subject_id="*",
    ))
    for _ in range(n_edges):
        d = f"d{rng.randrange(n_docs)}"
        kind = rng.random()
        kw = dict(resource_type="doc", resource_id=d,
                  resource_relation="reader" if rng.random() < 0.8 else "writer",
                  subject_type="user", subject_id=f"u{rng.randrange(n_users)}")
        if kind < 0.08:
            kw.update(subject_type="team",
                      subject_id=f"t{rng.randrange(n_teams)}",
                      subject_relation="member")
        elif kind < 0.11:
            kw.update(subject_type="team",
                      subject_id=f"t{rng.randrange(n_teams)}",
                      subject_relation="everyone")
            kw["resource_relation"] = "reader"
        elif kind < 0.13:
            kw.update(subject_id="*")
            kw["resource_relation"] = "reader"
        r = rel.Relationship(**kw)
        if rng.random() < 0.12:
            r = rel.Relationship(
                **{**r.__dict__, "caveat_name": "on_tuesday",
                   "caveat_context": {"day": "tuesday"} if rng.random() < 0.5
                   else {}},
            )
        if rng.random() < 0.07:
            r = rel.Relationship(
                **{**r.__dict__,
                   "expiration": dt.datetime.fromtimestamp(
                       (NOW + rng.randrange(-10**9, 10**12)) / 1e6,
                       tz=dt.timezone.utc,
                   )},
            )
        rels.append(r)
    return rels


def _checks(seed: int, n: int):
    rng = random.Random(seed + 1)
    out = []
    for _ in range(n):
        q = rel.must_from_triple(
            f"doc:d{rng.randrange(16)}", rng.choice(["view", "edit"]),
            f"user:u{rng.randrange(10)}",
        )
        if rng.random() < 0.4:
            q = q.with_caveat(
                "", {"day": rng.choice(["tuesday", "friday"])}
            )
        out.append(q)
    out.append(rel.must_from_tuple("doc:d0#view", "team:t1#member"))
    out.append(rel.must_from_triple("doc:nope", "view", "user:u0"))
    return out


class _World:
    """One seeded world: schema, snapshot, the independent host oracle,
    and a prepared engine per layout (built once, shared by the tests)."""

    def __init__(self, seed: int) -> None:
        self.cs = compile_schema(parse_schema(SCHEMA))
        self.rels = _random_world(seed, 120)
        self.snap = build_snapshot(1, self.cs, Interner(), self.rels,
                                   epoch_us=NOW)
        self.progs = {
            name: compile_cel(name, decl.params, decl.expression)
            for name, decl in self.cs.schema.caveats.items()
        }
        self.oracle = Oracle(self.cs, self.rels, self.progs, now_us=NOW)
        self.checks = _checks(seed, 40)
        self._engines = {}

    def engine(self, **cfg):
        key = tuple(sorted(cfg.items()))
        got = self._engines.get(key)
        if got is None:
            e = DeviceEngine(self.cs, EngineConfig.for_schema(self.cs, **cfg))
            got = self._engines[key] = (e, e.prepare(self.snap))
        return got


@functools.lru_cache(maxsize=None)
def _world(seed: int) -> _World:
    return _World(seed)


# ---------------------------------------------------------------------------
# checks: the chain against the host oracle, per layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [7, 11])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_chain_matches_oracle(layout, seed):
    """``definite`` ⇒ granted; ``¬possible ∧ ¬overflow`` ⇒ denied; the
    rest goes to the host as the client sends it (client.py: ``(p & ~d)
    | ovf`` through a SnapshotOracle) and lands on the oracle's verdict.
    The world must leave the device something to decide either way."""
    w = _world(seed)
    cfg = LAYOUTS[layout]
    engine, dsnap = w.engine(**cfg)
    meta = dsnap.flat_meta
    assert meta is not None
    if "flat_packed" in cfg:
        assert meta.packed, "layout should pack its tables"
    if cfg.get("flat_aligned"):
        assert meta.aligned, "layout should align its buckets"
    if "closure_source_cap" in cfg:
        assert meta.has_ovf, "world should spill the closure cap at 4"
    d, p, ovf = engine.check_batch(dsnap, w.checks, now_us=NOW)
    host = SnapshotOracle(w.snap, w.progs)
    granted = denied = 0
    for i, q in enumerate(w.checks):
        want = w.oracle.check_relationship(q)
        if d[i]:
            assert want == T, f"unsound definite for {q}"
            granted += 1
        elif not p[i] and not ovf[i]:
            assert want == F, f"possible misses oracle {want} for {q}"
            denied += 1
        else:
            got = host.check_relationship(q, now_us=NOW) == T
            assert got == (want == T), f"host resolution differs for {q}"
    assert granted and denied, (granted, denied)


# ---------------------------------------------------------------------------
# lookups: the frontier run probes against the host walker and the oracle
# ---------------------------------------------------------------------------


#: direction → (device entry, the oracle's walk, three argument tuples)
LOOKUPS = {
    "resources": (
        lookup_resources_device, "lookup_resources",
        [("doc", "view", "user", uid, "") for uid in ("u0", "u3", "u5")],
    ),
    "subjects": (
        lookup_subjects_device, "lookup_subjects",
        [("doc", did, "view", "user", "") for did in ("d0", "d1", "d3")],
    ),
}


@pytest.mark.parametrize("layout", ["packed", "packed+aligned"])
@pytest.mark.parametrize("direction", list(LOOKUPS))
def test_lookup_matches_walker(direction, layout):
    """LookupResources / LookupSubjects through the device frontier
    (engine/spmv.py run probes) return the answer sets of the host
    walker (``flat_rev_index=False``) and of the oracle's own walk."""
    w = _world(7)
    ef, df = w.engine(**LAYOUTS[layout])
    ew, dw = w.engine(flat_rev_index=False)
    device, walk, queries = LOOKUPS[direction]
    fac = lambda: Oracle(w.cs, w.rels, w.progs, now_us=NOW)  # noqa: E731
    m = metrics.default
    frontier0, walker0 = m.counter("lookups.frontier"), m.counter("lookups.walker")
    for args in queries:
        got = device(ef, df, *args, now_us=NOW, oracle_factory=fac)
        walked = device(ew, dw, *args, now_us=NOW, oracle_factory=fac)
        assert got == walked, args
        assert got == sorted(getattr(w.oracle, walk)(*args)), args
    assert m.counter("lookups.frontier") - frontier0 == len(queries)
    assert m.counter("lookups.walker") - walker0 == len(queries)


# ---------------------------------------------------------------------------
# latency tiers: pinned from the engine's jit, no retrace per qctx shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("aligned", [False, True], ids=["aligned-off", "aligned-on"])
def test_latency_tiers_no_retrace_on_world(aligned):
    """Warm same-tier dispatches pay zero compiles, with and without
    query context: each qctx shape pins once, the planes equal the
    throughput path's, and going back to a shape already served finds
    its pin."""
    w = _world(7)
    engine, dsnap = w.engine(flat_packed=True, flat_aligned=aligned)
    lp = engine.latency_path(dsnap)
    with_ctx = w.checks
    no_ctx = [q for q in w.checks if not q.caveat_context]
    assert 0 < len(no_ctx) < len(with_ctx)

    def same_planes(batch):
        got = engine.check_batch(dsnap, batch, now_us=NOW, latency=True)
        want = engine.check_batch(dsnap, batch, now_us=NOW)
        for a, b, name in zip(got, want, ("d", "p", "ovf")):
            assert np.array_equal(a, b), name

    served = lp.dispatch_count
    same_planes(with_ctx)
    same_planes(no_ctx)
    warm = lp.compile_count
    assert 1 <= warm <= 2
    for i in range(1, 5):
        same_planes(with_ctx[i:] + with_ctx[:i])
        same_planes(no_ctx[i:] + no_ctx[:i])
    assert lp.dispatch_count == served + 10, "a batch fell off the tiers"
    assert lp.compile_count == warm, (
        f"latency path retraced: {lp.compile_count - warm} extra"
    )


# ---------------------------------------------------------------------------
# the config: no field without a reader
# ---------------------------------------------------------------------------


def test_engine_config_has_no_unread_field():
    """Every EngineConfig field is read as an attribute somewhere in the
    package (its ``name: type = default`` line is not a read): a knob
    whose last reader was deleted must go with it."""
    root = pathlib.Path(gochugaru_tpu.__file__).parent
    src = "\n".join(p.read_text() for p in sorted(root.rglob("*.py")))
    unread = [
        f.name for f in dataclasses.fields(EngineConfig)
        if not re.search(r"\." + f.name + r"\b", src)
    ]
    assert not unread, unread
