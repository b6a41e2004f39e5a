"""Expiring grants on the flat device path, over GitHub-style RBAC whose
memberships and grants expire (org → team → repo; ``team#member``,
``org#member``, ``repo#maintainer``'s team share and ``repo#reader`` all
``with expiration``; ``org#admin`` and ``repo#org`` never expire).

Seeded small worlds go in through the columnar imports with their
expiries; every check is compared with a numpy reference over the live
edges alone and with the host oracle, and must be answered on the device.
Named cases sit beside the random ones: an expired reader, an expired team
membership that reaches both ``maintainer`` and ``org->member``, an expired
org-team share and an expired repo-team share, each with a live twin.

An expiry that falls inside the second of a check's instant is the one place
the device is not exact to the microsecond: the tables hold expiries as
whole seconds from the snapshot's epoch rounded up and the instant rounded
down, so such a grant holds until the next whole second, never less.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from gochugaru_tpu import consistency, new_tpu_evaluator, rel
from gochugaru_tpu.client import with_latency_mode
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.oracle import Oracle, SnapshotOracle, T
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background

SCHEMA = """
use expiration

definition user {}
definition team { relation member: user with expiration }
definition org {
    relation admin: user
    relation member: user with expiration | team#member with expiration
}
definition repo {
    relation org: org
    relation maintainer: user | team#member with expiration
    relation reader: user with expiration
    permission admin = org->admin + maintainer
    permission read = reader + admin + org->member
}
"""
U, TEAMS, O, R = 600, 60, 12, 3000
HOUR_US = 3600 * 1_000_000
CS = consistency.full()
HOST = ("checks.oracle", "checks.fallback_conditional", "checks.fallback_overflow")
#: (edge list, resource type, relation, subject type, subject relation)
SHAPES = (
    ("team_user", "team", "member", "user", ""),
    ("org_admin", "org", "admin", "user", ""),
    ("org_team", "org", "member", "team", "member"),
    ("org_user", "org", "member", "user", ""),
    ("repo_org", "repo", "org", "org", ""),
    ("repo_team", "repo", "maintainer", "team", "member"),
    ("repo_reader", "repo", "reader", "user", ""),
)
EXPIRING = ("team_user", "org_team", "org_user", "repo_team", "repo_reader")
#: the named cases: (repo, user) → granted, on objects of their own
#: (``r*``/``t*``/``o*``/``u*`` past the random world's ids)
NAMED = {
    "an expired reader": ("rx0", "ux0", False),
    "its live twin": ("rx1", "ux0", True),
    "an expired membership through maintainer": ("rx2", "ux1", False),
    "an expired membership through org->member": ("rx3", "ux1", False),
    "the live member through maintainer": ("rx2", "ux2", True),
    "the live member through org->member": ("rx3", "ux2", True),
    "an expired org-team share": ("rx4", "ux3", False),
    "its live twin share": ("rx5", "ux4", True),
    "an expired repo-team share": ("rx6", "ux5", False),
    "its live twin repo share": ("rx7", "ux6", True),
}


def named_edges(now_us: int):
    """The named cases' edges as (shape, resource, subject, expiry micros)."""
    live, gone = now_us + 30 * 24 * HOUR_US, now_us - 2 * HOUR_US
    return [
        ("repo_reader", "rx0", "ux0", gone), ("repo_reader", "rx1", "ux0", live),
        # tx0: ux1 expired, ux2 live; a maintainer share and an org's member
        ("team_user", "tx0", "ux1", gone), ("team_user", "tx0", "ux2", live),
        ("repo_team", "rx2", "tx0", live), ("org_team", "ox0", "tx0", live),
        ("repo_org", "rx3", "ox0", 0),
        # org-team shares: ox1's has expired, ox2's is live
        ("team_user", "tx1", "ux3", live), ("org_team", "ox1", "tx1", gone),
        ("repo_org", "rx4", "ox1", 0),
        ("team_user", "tx2", "ux4", live), ("org_team", "ox2", "tx2", live),
        ("repo_org", "rx5", "ox2", 0),
        # repo-team shares: rx6's has expired, rx7's is live
        ("team_user", "tx3", "ux5", live), ("repo_team", "rx6", "tx3", gone),
        ("team_user", "tx4", "ux6", live), ("repo_team", "rx7", "tx4", live),
        ("org_admin", "ox0", "ua", 0), ("org_admin", "ox1", "ua", 0),
        ("org_admin", "ox2", "ua", 0),
    ]


def build_world(seed: int, now_us: int) -> dict:
    """Edge lists of ids with expiries (0 for none): the random world, a
    tenth of every expiring list already expired, plus the named cases."""
    rng = np.random.default_rng(seed)

    def pairs(n_src, per, n_dst, src, dst):
        key = np.unique(np.repeat(np.arange(n_src), per) * n_dst
                        + rng.integers(0, n_dst, n_src * per))
        return [f"{src}{a}" for a in (key // n_dst).tolist()], \
            [f"{dst}{b}" for b in (key % n_dst).tolist()]

    w = {
        "team_user": pairs(TEAMS, 12, U, "t", "u"),
        "org_admin": pairs(O, 1, U, "o", "u"),
        "org_team": pairs(O, 2, TEAMS, "o", "t"),
        "org_user": pairs(O, 4, U, "o", "u"),
        "repo_org": pairs(R, 1, O, "r", "o"),
        "repo_team": pairs(R, 1, TEAMS, "r", "t"),
        "repo_reader": pairs(R, 2, U, "r", "u"),
    }
    out = {}
    for key, (res, subj) in w.items():
        n = len(res)
        exp = np.zeros(n, np.int64)
        if key in EXPIRING:
            exp = now_us + rng.integers(HOUR_US, 90 * 24 * HOUR_US, n)
            gone = rng.random(n) < 0.1
            exp[gone] = now_us - rng.integers(HOUR_US, 24 * HOUR_US, int(gone.sum()))
        out[key] = (res, subj, exp)
    for key, res, subj, exp in named_edges(now_us):
        r, s, e = out[key]
        out[key] = (r + [res], s + [subj], np.append(e, exp))
    return out


def reference(w, now_us: int):
    """``read(repo, user)`` from the live edges alone."""
    live = {k: {(r, s) for r, s, e in zip(res, subj, exp.tolist())
                if e == 0 or e > now_us}
            for k, (res, subj, exp) in w.items()}
    members = {}
    for t, u in live["team_user"]:
        members.setdefault(t, set()).add(u)
    org_of = {r: o for r, o in live["repo_org"]}

    def org_member(o, u):
        return ((o, u) in live["org_admin"] or (o, u) in live["org_user"]
                or any(u in members.get(t, ()) for oo, t in live["org_team"]
                       if oo == o))

    def read(r, u):
        return ((r, u) in live["repo_reader"]
                or any(u in members.get(t, ()) for rr, t in live["repo_team"]
                       if rr == r)
                or (r in org_of and org_member(org_of[r], u)))

    return read


def load(w):
    """A client holding ``w``: one columnar call an edge list, the
    expiring lists with their ``expirations``."""
    c = new_tpu_evaluator(with_latency_mode())
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    for key, rtype, relation, stype, srel in SHAPES:
        res, subj, exp = w[key]
        kw = {"expirations": exp} if key in EXPIRING else {}
        c.import_relationship_columns(
            ctx, resource_type=rtype, resource_ids=res, resource_relation=relation,
            subject_type=stype, subject_ids=subj, subject_relation=srel, **kw)
    return c


def probes(w, n: int, seed: int):
    """(repo, user) pairs: readers, members of the repo's team and of its
    org's teams (expired edges included), and uniform pairs."""
    rng = np.random.default_rng(seed)
    members = {}
    for t, u in zip(*w["team_user"][:2]):
        members.setdefault(t, []).append(u)
    org_teams = {}
    for o, t in zip(*w["org_team"][:2]):
        org_teams.setdefault(o, []).append(t)
    out = []
    readers, shares, orgs = w["repo_reader"], w["repo_team"], w["repo_org"]
    org_of = dict(zip(*orgs[:2]))
    for i in rng.integers(0, len(readers[0]), n // 4).tolist():
        out.append((readers[0][i], readers[1][i]))
    for i in rng.integers(0, len(shares[0]), n // 4).tolist():
        pool = members.get(shares[1][i], ["u0"])
        out.append((shares[0][i], pool[rng.integers(len(pool))]))
    for i in rng.integers(0, len(orgs[0]), n // 4).tolist():
        teams = org_teams.get(org_of[orgs[0][i]], [])
        pool = members.get(teams[rng.integers(len(teams))], ["u0"]) if teams else ["u0"]
        out.append((orgs[0][i], pool[rng.integers(len(pool))]))
    for r, u in zip(rng.integers(0, R, n - 3 * (n // 4)).tolist(),
                    rng.integers(0, U, n - 3 * (n // 4)).tolist()):
        out.append((f"r{r}", f"u{u}"))
    return out


def counters(names) -> list:
    snap = metrics.default.snapshot()
    return [snap.get(k, 0) for k in names]


@pytest.mark.parametrize("seed", [1, 2])
def test_device_answers_equal_the_reference_and_the_oracle(seed):
    now_us = time.time_ns() // 1000
    w = build_world(seed, now_us)
    c = load(w)
    pairs = probes(w, 2000, seed) + [(r, u) for r, u, _ in NAMED.values()]
    rels = [rel.must_from_triple(f"repo:{r}", "read", f"user:{u}") for r, u in pairs]
    read = reference(w, now_us)
    want = [read(r, u) for r, u in pairs]
    assert 0.2 < np.mean(want) < 0.9
    # an expired edge takes some grant away
    every = reference({k: (r, s, np.zeros_like(e)) for k, (r, s, e) in w.items()},
                      now_us)
    assert any(every(r, u) and not g for (r, u), g in zip(pairs, want))
    host = counters(HOST)
    done = counters(["checks.device_definite", "engine.expiry_batches"])
    got = c.check(background(), CS, *rels)
    assert got == want
    assert counters(HOST) == host
    gained = [a - b for a, b in zip(
        counters(["checks.device_definite", "engine.expiry_batches"]), done)]
    assert gained[0] == len(rels) and gained[1] >= 1
    snap = c.store.snapshot_for(CS)
    oracle = SnapshotOracle(snap, {})
    assert [oracle.check_relationship(r) == T for r in rels] == want
    (dsnap,) = c._dsnap_cache.values()
    meta = dsnap.flat_meta
    assert meta.gates_expiry and meta.e_hasexp and meta.us_hasexp
    assert meta.pf_has_u and not meta.pf_u_alllive and not meta.pf_s_alllive


@pytest.mark.parametrize("case", sorted(NAMED))
def test_a_named_case(case):
    now_us = time.time_ns() // 1000
    w = build_world(3, now_us)
    repo, user, granted = NAMED[case]
    assert reference(w, now_us)(repo, user) is granted
    c = _named_client(w)
    assert c.check(background(), CS, rel.must_from_triple(
        f"repo:{repo}", "read", f"user:{user}")) == [granted]


_CLIENTS = {}


def _named_client(w):
    if "named" not in _CLIENTS:
        _CLIENTS["named"] = load(w)
    return _CLIENTS["named"]


def test_a_world_without_expiries_gates_nothing():
    now_us = time.time_ns() // 1000
    w = build_world(4, now_us)
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA.replace(" with expiration", ""))
    for key, rtype, relation, stype, srel in SHAPES:
        res, subj, _ = w[key]
        c.import_relationship_columns(
            ctx, resource_type=rtype, resource_ids=res, resource_relation=relation,
            subject_type=stype, subject_ids=subj, subject_relation=srel)
    before = counters(["engine.expiry_batches", "prepare.expiry_s.count"])
    c.check(ctx, CS, rel.must_from_triple("repo:r1", "read", "user:u1"))
    assert counters(["engine.expiry_batches", "prepare.expiry_s.count"]) == before
    (dsnap,) = c._dsnap_cache.values()
    assert not dsnap.flat_meta.gates_expiry


def test_a_world_with_expiries_times_their_prepare():
    now_us = time.time_ns() // 1000
    before = counters(["prepare.expiry_s.count"])[0]
    c = load(build_world(5, now_us))
    c.check(background(), CS, rel.must_from_triple("repo:r1", "read", "user:u1"))
    # the snapshot's expiry seconds and the fold's until slices
    assert counters(["prepare.expiry_s.count"])[0] - before == 2


EPOCH_US = 1_700_000_000_000_000
EXPIRY_US = EPOCH_US + 5_300_000  # 5.3 s after the snapshot's epoch


@pytest.mark.parametrize("at_us,device,exact", [
    (EPOCH_US + 5_200_000, True, True),    # before the expiry: live on both
    (EPOCH_US + 5_300_000, True, False),   # at it: expired, the device a second late
    (EPOCH_US + 5_900_000, True, False),   # inside its second: still late
    (EPOCH_US + 6_000_000, False, False),  # the next whole second: exact again
])
def test_an_expiry_in_the_checks_second_grants_until_the_next_second(
        at_us, device, exact):
    import datetime as dt

    expiry = dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc) + dt.timedelta(
        microseconds=EXPIRY_US)
    rels = [
        rel.must_from_triple("repo:a", "reader", "user:x").with_expiration(expiry),
        rel.must_from_triple("team:t", "member", "user:y").with_expiration(expiry),
        rel.must_from_triple("repo:b", "maintainer", "team:t#member")
        .with_expiration(expiry + dt.timedelta(days=1)),
    ]
    cs = compile_schema(parse_schema(SCHEMA))
    snap = build_snapshot(1, cs, Interner(), rels, epoch_us=EPOCH_US)
    engine = DeviceEngine(cs, EngineConfig.for_schema(cs, flat_recursion=3))
    dsnap = engine.prepare(snap)
    assert dsnap.flat_meta is not None
    checks = [rel.must_from_triple("repo:a", "read", "user:x"),
              rel.must_from_triple("repo:b", "read", "user:y")]
    d, p, ovf = engine.check_batch(dsnap, checks, now_us=at_us)
    assert d.tolist() == p.tolist() == [device, device] and not ovf.any()
    oracle = Oracle(cs, rels, {}, now_us=at_us)
    assert [oracle.check_relationship(q) == T for q in checks] == [exact, exact]


#: the fold's slice tables: key + until rows where a slice carries
#: expiries, the 1-wide key column alone where it is all live
ROW_TABLES = {"csr_gdp": 3, "pfu_gku": 2}
KEY_TABLES = {"csr_gk": 1, "pfu_gk": 1}
SPLIT_COLUMNS = ("csr_d", "csr_p", "pfu_u")


def _fold_tables(dsnap) -> dict:
    return {k: a.shape for k, a in dsnap.arrays.items()
            if k in ROW_TABLES or k in KEY_TABLES or k in SPLIT_COLUMNS}


def test_an_expiring_fold_ships_one_row_table_a_slice():
    """Expiring memberships: each fold slice is ONE table of key + until
    rows (``csr_gdp`` = gk, d, p; ``pfu_gku`` = gk, until), padded as the
    key column was (-1 keys, 0 untils past the rows), and no split
    until column ships."""
    now_us = time.time_ns() // 1000
    c = load(build_world(6, now_us))
    c.check(background(), CS, rel.must_from_triple("repo:r1", "read", "user:u1"))
    (dsnap,) = c._dsnap_cache.values()
    shapes = _fold_tables(dsnap)
    assert sorted(shapes) == sorted(ROW_TABLES)
    for k, w in ROW_TABLES.items():
        a = np.asarray(dsnap.arrays[k])
        rows = a.shape[0]
        assert a.dtype == np.int32 and a.shape == (rows, w)
        assert rows & (rows - 1) == 0
        keys = a[:, 0]
        n = int((keys >= 0).sum())
        assert n and (keys[n:] == -1).all() and (a[n:, 1:] == 0).all()
        assert rows >= n + 64  # the pad every real slice start needs
    meta = dsnap.flat_meta
    assert meta.fold_until_rows and not meta.pf_s_alllive
    assert not meta.pf_u_alllive


EXP_RELS_US = EXPIRY_US  # x's membership and read grant end 5.3 s in
DAY_US = 24 * HOUR_US


def _at(us: int):
    import datetime as dt

    return dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc) + dt.timedelta(
        microseconds=us)


def _row_world():
    """team t0's members (x until 5.3 s after the epoch, y a day later)
    reach repo b through a maintainer share and repo c through org o's
    member share, and repo d through a share that ends with x: both fold
    slices carry until values."""
    return [
        rel.must_from_triple("team:t0", "member", "user:x").with_expiration(
            _at(EXP_RELS_US)),
        rel.must_from_triple("team:t0", "member", "user:y").with_expiration(
            _at(EXP_RELS_US + DAY_US)),
        rel.must_from_triple("repo:b", "maintainer", "team:t0#member")
        .with_expiration(_at(EXP_RELS_US + DAY_US)),
        rel.must_from_triple("org:o", "member", "team:t0#member")
        .with_expiration(_at(EXP_RELS_US + DAY_US)),
        rel.must_from_triple("repo:c", "org", "org:o"),
        rel.must_from_triple("repo:a", "reader", "user:x").with_expiration(
            _at(EXP_RELS_US)),
        rel.must_from_triple("repo:d", "maintainer", "team:t0#member")
        .with_expiration(_at(EXP_RELS_US)),
    ]


@pytest.mark.parametrize("stage", ["prepared", "member write",
                                   "member write to all live"])
def test_row_tables_answer_as_the_oracle_either_side_of_an_expiry(stage):
    """The device reads the fold's until slices from the row tables and
    answers as the host oracle a second before x's expiry and a second
    after it — on the prepared snapshot, after a membership write that
    reships the closure slice incrementally (a new expiring member in,
    y out), and after one that leaves every closure row live (the slice
    goes back to its key column and the row table is dropped)."""
    from gochugaru_tpu.store.delta import apply_delta

    rels = _row_world()
    cs = compile_schema(parse_schema(SCHEMA))
    interner = Interner()
    snap = build_snapshot(1, cs, interner, rels, epoch_us=EPOCH_US)
    engine = DeviceEngine(cs, EngineConfig.for_schema(cs, flat_recursion=3))
    dsnap = engine.prepare(snap)
    want = set(ROW_TABLES)
    users = ["user:x", "user:y"]
    if stage != "prepared":
        z = rel.must_from_triple("team:t0", "member", "user:z") \
            .with_expiration(_at(EXP_RELS_US + 2 * DAY_US))
        snap = apply_delta(snap, 2, [z], [rels[1]], interner=interner)
        dsnap = engine.prepare(snap, prev=dsnap)
        rels = [r for r in rels if r is not rels[1]] + [z]
        users.append("user:z")
        if stage == "member write to all live":
            w = rel.must_from_triple("team:t0", "member", "user:w")
            gone = [z, rels[0], rels[2]]  # z, x and the org share
            snap = apply_delta(snap, 3, [w], gone, interner=interner)
            dsnap = engine.prepare(snap, prev=dsnap)
            rels = [r for r in rels if all(r is not g for g in gone)] + [w]
            users.append("user:w")
            want = {"csr_gk", "pfu_gku"}
        assert dsnap.flat_meta.delta is not None  # the incremental reship
    assert set(_fold_tables(dsnap)) == want
    assert dsnap.flat_meta.fold_until_rows
    checks = [rel.must_from_triple(f"repo:{r}", "read", u)
              for r in "abcd" for u in users]
    seen = set()
    for at_us in (EXP_RELS_US - 1_300_000, EXP_RELS_US + 1_700_000):
        d, p, ovf = engine.check_batch(dsnap, checks, now_us=at_us)
        oracle = Oracle(cs, rels, {}, now_us=at_us)
        exact = [oracle.check_relationship(q) == T for q in checks]
        assert d.tolist() == p.tolist() == exact and not ovf.any()
        seen.add(tuple(exact))
    assert len(seen) == 2  # the expiries took grants away


def test_an_all_live_fold_keeps_the_key_columns_and_their_bytes():
    """A world with no expiry ships the fold's 1-wide key columns alone,
    with the shapes and the gathered-bytes model the split layout gave
    this world: 418 B a check, ``csr_gk`` 32 and ``pfu_gk`` 16 of it."""
    from gochugaru_tpu.utils.perf import gathered_bytes_model

    w = build_world(4, 1_700_000_000_000_000)
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA.replace(" with expiration", ""))
    for key, rtype, relation, stype, srel in SHAPES:
        res, subj, _ = w[key]
        c.import_relationship_columns(
            ctx, resource_type=rtype, resource_ids=res, resource_relation=relation,
            subject_type=stype, subject_ids=subj, subject_relation=srel)
    c.check(ctx, CS, rel.must_from_triple("repo:r1", "read", "user:u1"))
    (dsnap,) = c._dsnap_cache.values()
    assert _fold_tables(dsnap) == {"csr_gk": (1024, 1), "pfu_gk": (16384, 1)}
    meta = dsnap.flat_meta
    assert meta.pf_s_alllive and meta.pf_u_alllive and not meta.fold_until_rows
    model = gathered_bytes_model(dsnap)
    assert model.total == 418.0
    assert model.per_table["csr_gk"] == 32.0 and model.per_table["pfu_gk"] == 16.0
    assert not set(model.per_table) & (set(ROW_TABLES) | set(SPLIT_COLUMNS))


def test_fold_until_row_batches_counts_the_batches_that_read_row_tables():
    """``engine.fold_until_row_batches`` moves once a lowered batch on a
    snapshot whose fold slices carry until values, with
    ``engine.expiry_batches`` and ``intern.batch_calls``; on an all-live
    snapshot it never moves while batches go on being lowered."""
    names = ["engine.fold_until_row_batches", "engine.expiry_batches",
             "intern.batch_calls"]
    now_us = time.time_ns() // 1000
    w = build_world(7, now_us)
    c = load(w)
    rels = [rel.must_from_triple(f"repo:{r}", "read", f"user:{u}")
            for r, u in probes(w, 400, 7)]
    before = counters(names)
    for lo in range(0, len(rels), 100):  # four batches
        c.check(background(), CS, *rels[lo:lo + 100])
    moved = [a - b for a, b in zip(counters(names), before)]
    assert moved[0] == moved[1] == moved[2] >= 4
    live = new_tpu_evaluator()
    ctx = background()
    live.write_schema(ctx, SCHEMA.replace(" with expiration", ""))
    for key, rtype, relation, stype, srel in SHAPES:
        res, subj, _ = w[key]
        live.import_relationship_columns(
            ctx, resource_type=rtype, resource_ids=res, resource_relation=relation,
            subject_type=stype, subject_ids=subj, subject_relation=srel)
    before = counters(names)
    for lo in range(0, len(rels), 100):
        live.check(ctx, CS, *rels[lo:lo + 100])
    moved = [a - b for a, b in zip(counters(names), before)]
    assert moved[0] == moved[1] == 0 and moved[2] >= 4
