"""Pallas fused probe backend (engine/pallas.py).

Contract under test (ISSUE 20): with ``EngineConfig.pallas=True`` the
bucket probes behind checks run through the hand-fused Pallas kernels —
in INTERPRET mode under ``JAX_PLATFORMS=cpu`` — and every output plane
is BITWISE-identical to the ``pallas=False`` XLA gather chain, which is
the parity oracle.  ``pallas=None`` resolves to exactly the XLA path on
every platform (Mosaic refuses the kernels on a v5e — CHANGES.md, PR 21),
and the interpreter is reachable on the CPU backend only.  The
``pallas.dispatch`` fault
site classifies through the same retry envelope as the other dispatch
sites, and the perf ledger models the one-pass byte win per table.

Interpret-mode honesty: these tests prove correctness, not speed — the
byte win is a model (utils/perf.py ``pallas_bytes_model``), asserted
structurally here; its effect on a device is not measured.
"""

import datetime as dt
import random
from dataclasses import replace

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_admission_control,
    with_engine_config,
    with_latency_mode,
)
from gochugaru_tpu.engine import hash as H
from gochugaru_tpu.engine import packed as PK
from gochugaru_tpu.engine import pallas as P
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot
from gochugaru_tpu.utils import faults, metrics
from gochugaru_tpu.utils import perf as _perf
from gochugaru_tpu.utils.admission import OPEN, AdmissionConfig
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import UnavailableError

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


def _random_world(seed: int, n_edges: int):
    """Direct / wildcard / userset subjects, caveats with and without
    context, expirations, team chains deep enough to overflow a small
    closure cap — every fused probe site gets traffic."""
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


def _engine_pair(cs, snap, **cfg):
    """(xla, dsnap_x), (pallas, dsnap_p) engines over one snapshot."""
    ex = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=False, **cfg))
    ep = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True, **cfg))
    return (ex, ex.prepare(snap)), (ep, ep.prepare(snap))


@pytest.fixture(scope="module")
def world():
    cs = compile_schema(parse_schema(SCHEMA))
    snap = build_snapshot(1, cs, Interner(), _random_world(7, 120),
                          epoch_us=NOW)
    return cs, snap, _checks(7, 40)


# ---------------------------------------------------------------------------
# knob resolution / feature detect
# ---------------------------------------------------------------------------


def test_resolve_is_off_unless_demanded(monkeypatch):
    """The rule settled on the chip (CHANGES.md, PR 21): Mosaic refuses
    the kernels, so ``pallas=None`` resolves OFF on every platform — a
    TPU included — and only an explicit True turns them on."""
    import jax

    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert P.resolve(EngineConfig()) is False
        assert P.resolve(EngineConfig(pallas=False)) is False
        assert P.resolve(EngineConfig(pallas=True)) is True


def test_interpreter_only_on_cpu(monkeypatch):
    """``pallas=True`` off the CPU backend compiles for real or raises:
    the interpreter never stands in for a device compiler."""
    import jax

    assert P.interpret_mode() is True  # the suite runs on the CPU
    for backend in ("tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert P.interpret_mode() is False


def test_vmem_plan_pins_offsets_only():
    arrays = {
        "eh_off": np.zeros(1024, np.uint16),
        "eh_off_a": np.zeros(8, np.int32),
        "ehx": np.zeros((4096, 4), np.int32),       # block table: DMA'd
        "clx_al0": np.zeros((64, 16), np.int32),    # ladder level: pinned
        "big_off": np.zeros(6 << 20, np.int32),     # over budget
    }
    plan = P.vmem_plan(arrays)
    assert set(plan) == {"eh_off", "eh_off_a", "clx_al0"}
    total = P.publish_vmem(arrays)
    assert total == sum(plan.values())
    assert metrics.default.gauge("perf.vmem_resident_bytes") == float(total)


# ---------------------------------------------------------------------------
# interpret-mode bitwise parity, engine level
# ---------------------------------------------------------------------------


def test_engine_parity_random_world(world):
    """pallas=True == pallas=False on every output plane (d, p, ovf),
    including caveated checks with query context, wildcards, userset
    subjects, and expirations."""
    cs, snap, checks = world
    (ex, dx), (ep, dp) = _engine_pair(cs, snap)
    rx = ex.check_batch(dx, checks, now_us=NOW)
    rp = ep.check_batch(dp, checks, now_us=NOW)
    for a, b, name in zip(rx, rp, ("d", "p", "ovf")):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # knob-off restores the stock XLA path byte-for-byte: the default
    # (auto) config must produce the identical planes
    e0 = DeviceEngine(cs, EngineConfig.for_schema(cs))
    r0 = e0.check_batch(e0.prepare(snap), checks, now_us=NOW)
    for a, b, name in zip(rx, r0, ("d", "p", "ovf")):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_engine_parity_packed_and_aligned(world):
    """Packed uint16 layouts and the aligned width-stratified ladder run
    the same fused kernels (in-kernel decode / per-level salted row DMA)
    and stay bitwise with their XLA twins."""
    cs, snap, checks = world
    for cfg in ({"flat_packed": True},
                {"flat_packed": True, "flat_aligned": True}):
        (ex, dx), (ep, dp) = _engine_pair(cs, snap, **cfg)
        rx = ex.check_batch(dx, checks[:24], now_us=NOW)
        rp = ep.check_batch(dp, checks[:24], now_us=NOW)
        for a, b, name in zip(rx, rp, ("d", "p", "ovf")):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (cfg, name)


def test_engine_parity_closure_overflow(world):
    """A tiny closure width cap spills the nested team chains into the
    overflow table; the fused ovf/cl probes must agree lane-for-lane."""
    cs, snap, _ = world
    checks = _checks(11, 24)
    (ex, dx), (ep, dp) = _engine_pair(cs, snap, closure_source_cap=4)
    assert dx.flat_meta.has_ovf, "world should spill the closure cap at 4"
    rx = ex.check_batch(dx, checks, now_us=NOW)
    rp = ep.check_batch(dp, checks, now_us=NOW)
    for a, b, name in zip(rx, rp, ("d", "p", "ovf")):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


# ---------------------------------------------------------------------------
# kernel-level parity against the exact XLA reference chains
# ---------------------------------------------------------------------------


def test_kernel_modes_bitwise_unpacked():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    N, B = 300, 23
    k1 = rng.integers(0, 50, N).astype(np.int32)
    k2 = rng.integers(0, 30, N).astype(np.int32)
    pay = rng.integers(0, 1000, N).astype(np.int32)
    hi = H.build_hash([k1, k2], target_cap=4)
    tbl = H.interleave_buckets(hi, [k1, k2, pay, (pay // 2).astype(np.int32)])
    off = jnp.asarray(hi.off)
    q1 = rng.integers(-2, 52, B).astype(np.int32)  # negatives: dead lanes
    q2 = rng.integers(0, 31, B).astype(np.int32)
    qs = (jnp.asarray(q1), jnp.asarray(q2))

    ref = np.asarray(H.probe_block(off, jnp.asarray(tbl), hi.cap, qs))
    got = P.fused_probe(qs, off, jnp.asarray(tbl), cap=hi.cap, mode="block")
    assert np.array_equal(ref, np.asarray(got))

    hit = ((ref[:, :, 0] == q1[:, None]) & (ref[:, :, 1] == q2[:, None])
           & (q1 >= 0)[:, None] & (q2 >= 0)[:, None])
    got_any = P.fused_probe(qs, off, jnp.asarray(tbl), cap=hi.cap, mode="any")
    assert np.array_equal(hit.any(-1), np.asarray(got_any))

    d_ref = (hit & (ref[:, :, 2] > 500)).any(-1)
    p_ref = (hit & (ref[:, :, 3] > 500)).any(-1)
    d_got, p_got = P.fused_probe(
        qs, off, jnp.asarray(tbl), cap=hi.cap, mode="until2",
        now=jnp.int32(500),
    )
    assert np.array_equal(d_ref, np.asarray(d_got))
    assert np.array_equal(p_ref, np.asarray(p_got))

    # 2-D query lattice keeps its shape through the kernel
    q1m, q2m = q1[:20].reshape(4, 5), q2[:20].reshape(4, 5)
    refm = H.probe_block(
        off, jnp.asarray(tbl), hi.cap, (jnp.asarray(q1m), jnp.asarray(q2m))
    )
    gotm = P.fused_probe(
        (jnp.asarray(q1m), jnp.asarray(q2m)), off, jnp.asarray(tbl),
        cap=hi.cap, mode="block",
    )
    assert np.array_equal(np.asarray(refm), np.asarray(gotm))


def test_kernel_packed_and_runs_bitwise():
    """Packed uint16 rows + anchored uint16 offsets through the fused
    kernel == gather-then-decode_block; runs mode == the spmv bisect."""
    import jax.numpy as jnp

    from gochugaru_tpu.engine.packed import decode_block
    from gochugaru_tpu.engine.spmv import _field0_reader

    rng = np.random.default_rng(1)
    N, B = 500, 31
    k1 = rng.integers(0, 70, N).astype(np.int32)
    k2 = rng.integers(0, 40, N).astype(np.int32)
    pay = rng.integers(0, 100000, N).astype(np.int32)
    hi = H.build_hash([k1, k2], target_cap=4)
    tbl_raw = H.interleave_buckets(hi, [k1, k2, pay])
    spec = PK.make_spec([
        PK.col_range(-1, 70), PK.col_range(-1, 40), PK.col_range(-1, 100000),
    ])
    assert spec is not None
    packed = PK.pack_rows(tbl_raw, spec)
    off_res, off_anchor = PK.pack_off(hi.off)
    A = PK.OFF_ANCHOR_SHIFT
    q1 = rng.integers(-2, 72, B).astype(np.int32)
    q2 = rng.integers(0, 41, B).astype(np.int32)
    qs = (jnp.asarray(q1), jnp.asarray(q2))

    hh = (H.mix32([qs[0], qs[1]], jnp) & jnp.uint32(hi.size - 1)).astype(
        jnp.int32)
    start = (H.take_in_bounds(jnp.asarray(off_anchor), hh >> A)
             + H.take_in_bounds(jnp.asarray(off_res), hh).astype(jnp.int32))
    ref = decode_block(H.slice_blocks(jnp.asarray(packed), start, hi.cap),
                       spec)
    got = P.fused_probe(
        qs, jnp.asarray(off_res), jnp.asarray(packed), cap=hi.cap,
        spec=spec, off_a=jnp.asarray(off_anchor), ashift=A, mode="block",
    )
    assert np.array_equal(np.asarray(ref), np.asarray(got))

    # runs: sorted single-key buckets, in-kernel bisect vs the reference
    ks = np.sort(rng.integers(0, 60, N).astype(np.int32))
    v2 = rng.integers(0, 9, N).astype(np.int32)
    hi2 = H.build_hash([ks], target_cap=8)
    specr = PK.make_spec([PK.col_range(-1, 60), PK.col_range(-1, 9)])
    packedr = PK.pack_rows(H.interleave_buckets(hi2, [ks, v2]), specr)
    offr_res, offr_anchor = PK.pack_off(hi2.off)
    keys = jnp.asarray(rng.integers(-2, 62, B).astype(np.int32))

    col0 = _field0_reader(specr, 2)

    def offread(idx):
        return (H.take_in_bounds(jnp.asarray(offr_anchor), idx >> A)
                + H.take_in_bounds(jnp.asarray(offr_res), idx).astype(
                    jnp.int32))

    h2 = (H.mix32([keys], jnp) & jnp.uint32(hi2.size - 1)).astype(jnp.int32)
    s2, e2 = offread(h2), offread(h2 + 1)
    last = packedr.shape[0] - 1
    steps = max(int(hi2.cap).bit_length(), 1)

    def bisect(left):
        lo, n = s2, e2 - s2
        for _ in range(steps):
            alive = n > 0
            half = n >> 1
            mid = lo + half
            v = col0(jnp.asarray(packedr), jnp.clip(mid, 0, last))
            go = alive & ((v < keys) if left else (v <= keys))
            lo = jnp.where(go, mid + 1, lo)
            n = jnp.where(go, n - half - 1, jnp.where(alive, half, 0))
        return lo

    lo_ref = bisect(True)
    ln_ref = bisect(False) - lo_ref
    dead = keys < 0
    lo_ref = jnp.where(dead, 0, lo_ref)
    ln_ref = jnp.where(dead, 0, ln_ref)
    lo_got, ln_got = P.fused_probe(
        (keys,), jnp.asarray(offr_res), jnp.asarray(packedr), cap=hi2.cap,
        spec=specr, off_a=jnp.asarray(offr_anchor), ashift=A, mode="runs",
    )
    assert np.array_equal(np.asarray(lo_ref), np.asarray(lo_got))
    assert np.array_equal(np.asarray(ln_ref), np.asarray(ln_got))


def test_lookup_parity_pallas(world):
    """The SpMV/SpMM run probes behind LookupResources/LookupSubjects
    route through the fused ``runs`` kernel and return the identical
    answer sets."""
    from gochugaru_tpu.caveats import compile_cel
    from gochugaru_tpu.engine.lookup import (
        lookup_resources_device,
        lookup_subjects_device,
    )
    from gochugaru_tpu.engine.oracle import Oracle

    cs, snap, _ = world
    rels = _random_world(7, 120)
    progs = {
        name: compile_cel(name, decl.params, decl.expression)
        for name, decl in cs.schema.caveats.items()
    }
    (ex, dx), (ep, dp) = _engine_pair(cs, snap)
    fac = lambda: Oracle(cs, rels, progs, now_us=NOW)  # noqa: E731
    for uid in ("u0", "u3", "u5"):
        rx = lookup_resources_device(ex, dx, "doc", "view", "user", uid, "",
                                     now_us=NOW, oracle_factory=fac)
        rp = lookup_resources_device(ep, dp, "doc", "view", "user", uid, "",
                                     now_us=NOW, oracle_factory=fac)
        assert rx == rp, uid
    for did in ("d0", "d1", "d3"):
        sx = lookup_subjects_device(ex, dx, "doc", did, "view", "user", "",
                                    now_us=NOW, oracle_factory=fac)
        sp = lookup_subjects_device(ep, dp, "doc", did, "view", "user", "",
                                    now_us=NOW, oracle_factory=fac)
        assert sx == sp, did


# ---------------------------------------------------------------------------
# latency-tier pins: no retrace with the fused kernels
# ---------------------------------------------------------------------------


def test_latency_pins_no_retrace_with_pallas(world):
    """Warm same-tier dispatches through the pallas path pay ZERO extra
    compiles — resolve() is deterministic per config, so the pinned
    executables keep their no-retrace contract."""
    cs, snap, _ = world
    ep = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True))
    dp = ep.prepare(snap)
    lp = ep.latency_path(dp)
    slot = cs.slot_of_name
    rng = np.random.default_rng(5)
    B = 24
    docs = [snap.interner.node("doc", f"d{i}") for i in range(8)]
    users = [snap.interner.node("user", f"u{i}") for i in range(8)]
    q_res = rng.choice(np.asarray(docs, np.int64), B).astype(np.int32)
    q_perm = np.full(B, slot["view"], np.int32)
    q_subj = rng.choice(np.asarray(users, np.int64), B).astype(np.int32)
    out = lp.dispatch_columns(q_res, q_perm, q_subj, now_us=NOW)
    assert out is not None
    warm = lp.compile_count
    assert warm >= 1
    for i in range(1, 7):
        d, p, o = lp.dispatch_columns(
            np.roll(q_res, i), q_perm, np.roll(q_subj, i), now_us=NOW
        )
        dd, pp, oo = ep.check_columns(
            dp, np.roll(q_res, i), q_perm, np.roll(q_subj, i), now_us=NOW
        )
        assert (d == dd).all() and (p == pp).all() and (o == oo).all()
    assert lp.compile_count == warm, (
        f"pallas latency path retraced: {lp.compile_count - warm} extra"
    )


# ---------------------------------------------------------------------------
# chaos: pallas.dispatch classifies + reroutes like any dispatch fault
# ---------------------------------------------------------------------------


def test_pallas_fault_site_gated_by_config(world):
    """The site fires only when the config resolves pallas on: the XLA
    engine never reaches it, the pallas engine raises the classified
    transient error."""
    cs, snap, checks = world
    (ex, dx), (ep, dp) = _engine_pair(cs, snap)
    with faults.armed("pallas.dispatch") as spec:
        ex.check_batch(dx, checks[:4], now_us=NOW)  # XLA: site unreachable
        assert spec.hits == 0
        with pytest.raises(UnavailableError):
            ep.check_batch(dp, checks[:4], now_us=NOW)
        assert spec.fired == 1


def test_breaker_reforms_on_pallas_failures():
    """Consecutive pallas.dispatch failures on the pinned latency path
    trip the breaker exactly like latency-path failures: while OPEN the
    traffic re-forms onto the batch path, and answers never change."""
    c = new_tpu_evaluator(
        with_latency_mode(),
        with_engine_config(EngineConfig(pallas=True)),
        with_admission_control(
            AdmissionConfig(breaker_threshold=2, breaker_cooldown_s=60.0)
        ),
    )
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """)
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("doc:a", "reader", "user:u1"))
    c.write(ctx, txn)
    checks = [
        rel.must_from_triple("doc:a", "read", "user:u1"),
        rel.must_from_triple("doc:a", "read", "user:u2"),
    ]
    m = metrics.default
    assert c.check(ctx, consistency.full(), *checks) == [True, False]

    trips_before = m.counter("breaker.trips")
    with faults.armed("pallas.dispatch", times=2):
        # envelope retries through the two injected failures and lands
        # on the batch path with the site spent
        assert c.check(ctx, consistency.full(), *checks) == [True, False]
    assert m.counter("breaker.trips") == trips_before + 1
    assert c._admission.breaker.state == OPEN

    # while OPEN: latency traffic re-formed onto the batch path
    lat_before = m.counter("latency.dispatches")
    rerouted_before = m.counter("breaker.latency_rerouted")
    assert c.check(ctx, consistency.full(), *checks) == [True, False]
    assert m.counter("latency.dispatches") == lat_before
    assert m.counter("breaker.latency_rerouted") == rerouted_before + 1


# ---------------------------------------------------------------------------
# perf ledger: one-pass byte model + VMEM residency gauge
# ---------------------------------------------------------------------------


def test_prepare_publishes_vmem_and_byte_model(world):
    cs, snap, _ = world
    metrics.default.set_gauge("perf.vmem_resident_bytes", 0.0)
    ep = DeviceEngine(cs, EngineConfig.for_schema(cs, pallas=True,
                                                  flat_packed=True))
    dp = ep.prepare(snap)
    assert metrics.default.gauge("perf.vmem_resident_bytes") > 0
    assert metrics.default.gauge("perf.pallas.bytes_saved_per_check") > 0

    model = _perf.pallas_bytes_model(dp)
    assert model, "pallas byte model empty"
    saved_tables = {t for t, row in model.items() if row["saved"] > 0}
    # the direct-edge probe table must show the one-pass win
    assert any(t.startswith("ehx") or t == "eh_off" for t in saved_tables), (
        sorted(saved_tables))
    for t, row in model.items():
        assert row["xla"] >= row["pallas"], (t, row)
        assert row["saved"] == row["xla"] - row["pallas"], (t, row)
    # XLA-only prepare leaves the pallas gauges untouched
    metrics.default.set_gauge("perf.pallas.bytes_saved_per_check", -1.0)
    e0 = DeviceEngine(cs, EngineConfig.for_schema(cs))
    e0.prepare(snap)
    assert metrics.default.gauge("perf.pallas.bytes_saved_per_check") == -1.0
