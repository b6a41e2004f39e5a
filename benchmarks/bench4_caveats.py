"""BASELINE config 4 — multi-tenant SaaS with caveats at 100M edges:
on-device CEL caveat predicate evaluation (caveats/device.py).

Every grant edge carries a ``same_tenant`` caveat whose stored context
pins the edge's tenant; the query context supplies the caller's tenant.
The predicate (string equality + int tier comparison) runs inside the
jitted check — zero host fallbacks is part of the assertion.

Size note: 100M edges ≈ 3.4 GB of padded int32 columns on device.  Use
``--edges`` to scale down on small hosts; the driver-facing headline
(bench.py) stays config 2.
"""

import argparse

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from benchmarks.common import (
    start_backend,
    NORTH_STAR_P99_MS,
    NORTH_STAR_RATE,
    emit,
    emit_small_batch_row,
    latency_percentiles,
    note,
    time_steady,
)

SCHEMA = """
caveat same_tenant(tenant string, edge_tenant string, tier int) {
    tenant == edge_tenant && tier >= 1
}
definition user {}
definition org { relation admin: user }
definition item {
    relation org: org
    relation holder: user with same_tenant
    permission access = holder + org->admin
}
"""

EPOCH = 1_700_000_000_000_000


def build_world(n_edges: int, n_tenants: int = 4096):
    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner
    from gochugaru_tpu.store.snapshot import build_snapshot_from_columns

    cs = compile_schema(parse_schema(SCHEMA))
    interner = Interner()
    rng = np.random.default_rng(31)

    n_users = 200_000
    n_items = max(n_edges // 10, 1000)
    n_orgs = 2000
    users = np.array([interner.node("user", f"u{i}") for i in range(n_users)], np.int64)
    orgs = np.array([interner.node("org", f"o{i}") for i in range(n_orgs)], np.int64)
    items = np.array([interner.node("item", f"i{i}") for i in range(n_items)], np.int64)
    slot = cs.slot_of_name
    cid = cs.caveat_ids["same_tenant"]

    # shared stored-context rows: one per tenant (contexts are deduped by
    # construction — 100M edges share n_tenants dicts)
    contexts = [{"edge_tenant": f"t{t}", "tier": 2} for t in range(n_tenants)]

    n_holder = n_edges - n_items - n_orgs
    res = np.concatenate([
        rng.choice(items, n_holder),
        items,  # org edge per item
        orgs,  # admin per org
    ])
    rel = np.concatenate([
        np.full(n_holder, slot["holder"], np.int64),
        np.full(n_items, slot["org"], np.int64),
        np.full(n_orgs, slot["admin"], np.int64),
    ])
    subj = np.concatenate([
        rng.choice(users, n_holder),
        rng.choice(orgs, n_items),
        rng.choice(users, n_orgs),
    ])
    srel = np.full(res.shape[0], -1, np.int64)
    caveat = np.concatenate([
        np.full(n_holder, cid, np.int32),
        np.zeros(n_items + n_orgs, np.int32),
    ])
    ctx = np.concatenate([
        rng.integers(0, n_tenants, n_holder).astype(np.int32),
        np.full(n_items + n_orgs, -1, np.int32),
    ])

    snap = build_snapshot_from_columns(
        1, cs, interner,
        res=res, rel=rel, subj=subj, srel=srel,
        caveat=caveat, ctx=ctx, contexts=contexts,
        epoch_us=EPOCH,
    )
    return cs, snap, users, items, slot, n_tenants


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edges", type=int, default=100_000_000)
    ap.add_argument("--batch", type=int, default=100_000)
    args = ap.parse_args()
    note(f"platform={start_backend()}")

    from gochugaru_tpu.engine.device import DeviceEngine

    cs, snap, users, items, slot, n_tenants = build_world(args.edges)
    note(f"edges={snap.num_edges} contexts={len(snap.contexts)}")
    engine = DeviceEngine(cs)
    assert not engine.caveat_plan.host_only[cs.caveat_ids["same_tenant"]]
    dsnap = engine.prepare(snap)
    from benchmarks.common import join_lookup_prewarm

    join_lookup_prewarm(timeout=600)

    rng = np.random.default_rng(3)
    B = 1 << (args.batch - 1).bit_length()
    # half the queries target real holder edges (the caveat predicate must
    # actually run: right tenant → grant, wrong tenant → definite deny);
    # the other half are random misses
    holder_rows = np.nonzero(snap.e_rel == slot["holder"])[0]
    hit_rows = rng.choice(holder_rows, B // 2)
    q_res = np.concatenate([
        snap.e_res[hit_rows], rng.choice(items, B - B // 2).astype(np.int32),
    ])
    q_subj = np.concatenate([
        snap.e_subj[hit_rows], rng.choice(users, B - B // 2).astype(np.int32),
    ])
    q_perm = np.full(B, slot["access"], np.int32)
    # each query carries its caller's tenant + tier in request context;
    # for the edge-hitting half, 50% use the edge's own tenant (→ True)
    qctx_rows = [{"tenant": f"t{t}", "tier": 2} for t in range(n_tenants)]
    edge_tenant = snap.e_ctx[hit_rows].astype(np.int64)
    match = rng.random(B // 2) < 0.5
    hit_tenants = np.where(
        match, edge_tenant, (edge_tenant + 1) % n_tenants
    )
    q_ctx = np.concatenate([
        hit_tenants, rng.integers(0, n_tenants, B - B // 2),
    ]).astype(np.int32)

    def dispatch():  # pipelined device dispatch, no per-call readback
        return engine.check_columns(
            dsnap, q_res, q_perm, q_subj,
            q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=EPOCH, fetch=False,
        )

    def roundtrip():  # end-to-end including the device→host fetch
        return engine.check_columns(
            dsnap, q_res, q_perm, q_subj,
            q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=EPOCH,
        )

    dt = time_steady(dispatch, reps=5)
    rate = B / dt
    d, p, ovf = roundtrip()
    conditional = int((p & ~d).sum())
    note(
        f"batch={B} step={dt*1000:.1f}ms granted={int(d.sum())}"
        f" conditional(host-fallback)={conditional} overflow={int(ovf.sum())}"
    )
    emit(
        "caveated_100m_bulk_check_throughput", rate, "checks/sec/chip",
        rate / NORTH_STAR_RATE, edges=int(snap.num_edges), batch=int(B),
    )
    p50, p99, mean = latency_percentiles(roundtrip, reps=20)
    emit(
        "caveated_100m_batch_p99_latency", p99, "ms",
        NORTH_STAR_P99_MS / max(p99, 1e-9),
        edges=int(snap.num_edges), batch=int(B),
    )
    note(f"p50={p50:.2f}ms p99={p99:.2f}ms mean={mean:.2f}ms")

    # latency-mode small batch at spec scale (engine/latency.py), with
    # on-device caveat evaluation live: an interactive dispatch carries
    # its own (small) distinct-context slice, not the world's 4096 —
    # the per-dispatch qctx encode is honest host-lowering cost
    try:
        SB = 2048
        sb_tenants = 8
        sb_rows = [{"tenant": f"t{t}", "tier": 2} for t in range(sb_tenants)]
        emit_small_batch_row(
            "caveated_100m_small_batch_p99_latency", engine, dsnap,
            q_res[:SB].copy(), q_perm[:SB].copy(), q_subj[:SB].copy(),
            q_ctx=(q_ctx[:SB] % sb_tenants).astype(np.int32),
            qctx_rows=sb_rows, edges=int(snap.num_edges), now_us=EPOCH,
        )
    except Exception as e:  # optional row must never cost the main ones
        note(f"small-batch latency section failed: {type(e).__name__}: {e}")

    # sub-batch pipeline (VERDICT r04 item 8): the same B-item bulk
    # request dispatched as queued 32k sub-batches — per-sub-batch
    # completion latency is the tail a streaming consumer sees, and the
    # whole-request rate must hold
    import time as _t

    PB = engine._pipeline_batch() or 32_768
    def pipelined_once():
        lats = []
        t_start = _t.perf_counter()
        t_prev = t_start
        n = 0
        for lo, hi, d2, p2, o2 in engine.check_columns_pipelined(
            dsnap, q_res, q_perm, q_subj,
            q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=EPOCH, sub_batch=PB,
        ):
            t_now = _t.perf_counter()
            lats.append((t_now - t_prev) * 1000)
            t_prev = t_now
            n += hi - lo
        return (_t.perf_counter() - t_start), lats, n

    try:
        pipelined_once()  # warm the PB-bucket compilation
        all_lats = []
        total_s = 0.0
        total_n = 0
        for _ in range(6):
            dt2, lats, n = pipelined_once()
            all_lats += lats
            total_s += dt2
            total_n += n
        pl = np.asarray(all_lats)
        pp99 = float(np.percentile(pl, 99))
        prate = total_n / total_s
        emit(
            "caveated_100m_pipelined_subbatch_p99_latency", pp99, "ms",
            NORTH_STAR_P99_MS / max(pp99, 1e-9),
            edges=int(snap.num_edges), batch=int(PB),
        )
        emit(
            "caveated_100m_pipelined_throughput", prate, "checks/sec/chip",
            prate / NORTH_STAR_RATE, edges=int(snap.num_edges), batch=int(B),
        )
        note(
            f"pipelined PB={PB}: sub-batch p50={np.percentile(pl,50):.2f}ms "
            f"p99={pp99:.2f}ms rate={prate:,.0f}/s"
        )
    except Exception as e:  # optional metrics must never cost the main rows
        note(f"pipelined section failed: {type(e).__name__}: {e}")


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
