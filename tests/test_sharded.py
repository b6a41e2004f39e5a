"""Mesh-sharded engine tests on the 8-virtual-device CPU mesh (conftest
forces XLA_FLAGS=--xla_force_host_platform_device_count=8) — the moral
equivalent of the reference's dockerized cluster test (SURVEY.md §4)."""

import random

import jax
import numpy as np
import pytest

from gochugaru_tpu import rel
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.oracle import T, U, Oracle
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.parallel import ShardedEngine, make_mesh
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import metrics

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

SCHEMA = """
definition user {}
definition team { relation member: user }
definition org {
    relation admin: user
    relation member: user | team#member
}
definition repo {
    relation org: org
    relation maintainer: user | team#member
    relation reader: user
    permission admin = org->admin + maintainer
    permission read = reader + admin + org->member
}
"""


def build_world(seed=7):
    rng = random.Random(seed)
    triples = []
    users = [f"user:u{i}" for i in range(40)]
    teams = [f"team:t{i}" for i in range(6)]
    orgs = [f"org:o{i}" for i in range(3)]
    repos = [f"repo:r{i}" for i in range(20)]
    for t in teams:
        for u in rng.sample(users, 8):
            triples.append((f"{t}#member", u))
    for o in orgs:
        triples.append((f"{o}#admin", rng.choice(users)))
        for t in rng.sample(teams, 2):
            triples.append((f"{o}#member", f"{t}#member"))
    for r in repos:
        triples.append((f"{r}#org", rng.choice(orgs)))
        triples.append((f"{r}#maintainer", f"{rng.choice(teams)}#member"))
        for u in rng.sample(users, 3):
            triples.append((f"{r}#reader", u))
    rels = [rel.must_from_tuple(*t) for t in triples]
    cs = compile_schema(parse_schema(SCHEMA))
    interner = Interner()
    snap = build_snapshot(1, cs, interner, rels, epoch_us=1_700_000_000_000_000)
    oracle = Oracle(cs, rels, now_us=1_700_000_000_000_000)
    queries = []
    rng2 = random.Random(seed + 1)
    for r in [f"repo:r{i}" for i in range(20)]:
        for u in rng2.sample(users, 8):
            queries.append(rel.must_from_triple(r, rng2.choice(["read", "admin"]), u))
    return cs, snap, oracle, queries


@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_matches_oracle_and_single_device(shape):
    data, model = shape
    cs, snap, oracle, queries = build_world()
    mesh = make_mesh(data, model)
    sharded = ShardedEngine(cs, mesh)
    dsnap = sharded.prepare(snap)
    d, p, ovf = sharded.check_batch(dsnap, queries, now_us=1_700_000_000_000_000)

    single = DeviceEngine(cs)
    sd, sp, sovf = single.check_batch(
        single.prepare(snap), queries, now_us=1_700_000_000_000_000
    )
    np.testing.assert_array_equal(d, sd)
    np.testing.assert_array_equal(p, sp)
    for i, q in enumerate(queries):
        tri = oracle.check_relationship(q)
        assert not ovf[i]
        assert d[i] == (tri == T), f"{q}: sharded={d[i]} oracle={tri}"


def test_edge_sharded_folder_recursion():
    # recursion + arrows across edge shards: children live on any shard
    schema = """
    definition user {}
    definition folder {
        relation parent: folder
        relation owner: user
        permission view = owner + parent->view
    }
    """
    triples = [("folder:f0#owner", "user:root")]
    for i in range(1, 6):
        triples.append((f"folder:f{i}#parent", f"folder:f{i-1}"))
    rels = [rel.must_from_tuple(*t) for t in triples]
    cs = compile_schema(parse_schema(schema))
    snap = build_snapshot(1, cs, Interner(), rels, epoch_us=1_700_000_000_000_000)
    mesh = make_mesh(2, 4)
    eng = ShardedEngine(cs, mesh)
    dsnap = eng.prepare(snap)
    qs = [
        rel.must_from_triple("folder:f5", "view", "user:root"),
        rel.must_from_triple("folder:f3", "view", "user:root"),
        rel.must_from_triple("folder:f5", "view", "user:other"),
    ]
    d, p, ovf = eng.check_batch(dsnap, qs, now_us=1_700_000_000_000_000)
    assert list(d) == [True, True, False]
    assert not ovf.any()


def test_array_keys_match_host_arrays():
    # ShardedEngine derives its shard_map specs from
    # DeviceEngine.ARRAY_COLUMN_KEYS; _host_arrays must emit exactly that
    # column set or the in_specs pytree desyncs (silent drift hazard)
    cs = compile_schema(parse_schema(SCHEMA))
    rels = [rel.must_from_tuple("repo:r#reader", "user:u")]
    snap = build_snapshot(1, cs, Interner(), rels, epoch_us=1_700_000_000_000_000)
    eng = DeviceEngine(cs)
    host = eng._host_arrays(snap)
    assert set(host) == set(DeviceEngine.ARRAY_COLUMN_KEYS)


def test_sharded_check_columns_matches_check_batch():
    cs, snap, oracle, queries = build_world(seed=3)
    mesh = make_mesh(4, 2)
    eng = ShardedEngine(cs, mesh)
    dsnap = eng.prepare(snap)
    checks = queries[:48]
    d0, p0, o0 = eng.check_batch(dsnap, checks, now_us=1_700_000_000_000_000)
    interner = snap.interner
    slot = cs.slot_of_name
    q_res = np.array(
        [interner.lookup(x.resource_type, x.resource_id) for x in checks], np.int32
    )
    q_perm = np.array([slot[x.resource_relation] for x in checks], np.int32)
    q_subj = np.array(
        [interner.lookup(x.subject_type, x.subject_id) for x in checks], np.int32
    )
    d1, p1, o1 = eng.check_columns(
        dsnap, q_res, q_perm, q_subj, now_us=1_700_000_000_000_000
    )
    assert list(d0) == list(np.asarray(d1))
    assert list(p0) == list(np.asarray(p1))


def test_sharded_check_columns_reflexive_self():
    cs, snap, oracle, queries = build_world(seed=5)
    mesh = make_mesh(4, 2)
    eng = ShardedEngine(cs, mesh)
    dsnap = eng.prepare(snap)
    interner = snap.interner
    slot = cs.slot_of_name
    # team:t0#member checked against itself → reflexive True
    t0 = interner.lookup("team", "t0")
    q_res = np.array([t0], np.int32)
    q_perm = np.array([slot["member"]], np.int32)
    q_subj = np.array([t0], np.int32)
    q_srel = np.array([slot["member"]], np.int32)
    d, p, o = eng.check_columns(
        dsnap, q_res, q_perm, q_subj, q_srel=q_srel,
        now_us=1_700_000_000_000_000,
    )
    assert bool(np.asarray(d)[0])


def test_sharded_two_phase_builds_subject_rows_per_block():
    """Without the flat kernel the shard_mapped two-phase program reads
    the subject rows of each data shard's block: built at its dispatch
    (``engine.subject_rows`` moves once a batch), answers as the oracle."""
    cs, snap, oracle, queries = build_world(seed=11)
    eng = ShardedEngine(
        cs, make_mesh(4, 2), EngineConfig.for_schema(cs, use_flat=False))
    dsnap = eng.prepare(snap)
    assert dsnap.flat_meta is None
    before = metrics.default.counter("engine.subject_rows")
    d, p, ovf = eng.check_batch(dsnap, queries, now_us=1_700_000_000_000_000)
    assert metrics.default.counter("engine.subject_rows") - before == 1
    assert not ovf.any()
    assert [bool(x) for x in d] == [
        oracle.check_relationship(q) == T for q in queries]


def test_sharded_flat_slot_chunking():
    """More distinct permissions in one batch than flat_max_slots: the
    sharded flat dispatch must chunk the slot set (bounded compiles) and
    still answer every query exactly."""
    cs, snap, oracle, queries = build_world()
    mesh = make_mesh(2, 4)
    from gochugaru_tpu.engine.plan import EngineConfig

    eng = ShardedEngine(cs, mesh, EngineConfig.for_schema(cs, flat_max_slots=1))
    dsnap = eng.prepare(snap)
    assert dsnap.flat_meta is not None and dsnap.flat_meta.sharded
    # queries mix 'read'/'admin' (2 slots) + relation slots via tuples
    mixed = queries[:48] + [
        rel.must_from_tuple("repo:r1#reader", "user:u1"),
        rel.must_from_tuple("team:t0#member", "user:u0"),
    ]
    d, p, ovf = eng.check_batch(dsnap, mixed, now_us=1_700_000_000_000_000)
    single = DeviceEngine(cs)
    sd, sp, sovf = single.check_batch(
        single.prepare(snap), mixed, now_us=1_700_000_000_000_000
    )
    np.testing.assert_array_equal(d, sd)
    np.testing.assert_array_equal(p, sp)
    np.testing.assert_array_equal(ovf, sovf)


def test_sharded_meta_kernel_mismatch_raises():
    """A bucket-sharded FlatMeta must not silently build a single-chip
    kernel (and vice versa) — the geometry is incompatible."""
    cs, snap, oracle, queries = build_world()
    mesh = make_mesh(2, 4)
    eng = ShardedEngine(cs, mesh)
    dsnap = eng.prepare(snap)
    from gochugaru_tpu.engine.flat import make_flat_fn

    with pytest.raises(ValueError):
        make_flat_fn(
            eng.compiled, eng.plan, eng.config, dsnap.flat_meta, (),
            caveat_plan=eng.caveat_plan,
        )


def test_sharded_flat_features_world():
    """Caveats, expirations, wildcards, nested groups, and folder
    recursion under the bucket-sharded flat kernel: every plane must
    match the single-chip flat engine exactly (the CEL VM runs on
    replicated context tables; gates ride the sharded blocks)."""
    import test_flat_engine as tfe

    rng = random.Random(4)
    rels = tfe.build_feature_world(rng)
    cs = compile_schema(parse_schema(tfe.FEATURES))
    interner = Interner()
    snap = build_snapshot(1, cs, interner, rels, epoch_us=tfe.NOW)
    checks = tfe.make_checks(rng, 10, 10, n=64)
    from gochugaru_tpu.engine.plan import EngineConfig

    cfg = EngineConfig.for_schema(cs, flat_recursion=3, flat_max_width=32)
    single = DeviceEngine(cs, cfg)
    sd, sp, sovf = single.check_batch(
        single.prepare(snap), checks, now_us=tfe.NOW
    )
    for shape in [(4, 2), (1, 8)]:
        mesh = make_mesh(*shape)
        eng = ShardedEngine(cs, mesh, cfg)
        dsnap = eng.prepare(snap)
        assert dsnap.flat_meta is not None and dsnap.flat_meta.sharded
        d, p, ovf = eng.check_batch(dsnap, checks, now_us=tfe.NOW)
        for i, q in enumerate(checks):
            assert bool(d[i]) == bool(sd[i]), f"{shape} definite differs: {q}"
            assert bool(p[i]) == bool(sp[i]), f"{shape} possible differs: {q}"
            assert bool(ovf[i]) == bool(sovf[i]), f"{shape} ovf differs: {q}"
