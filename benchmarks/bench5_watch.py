"""BASELINE config 5 — Leopard-scale Watch-driven incremental re-index.

Measures the path that keeps a live index fresh: a stream of relationship
updates (the Watch feed, client/client.go:364-413) is folded into the
current snapshot via O(E + D log E) delta materialization
(store/delta.py), and the DEVICE side advances incrementally — the base
revision's resident tables are reused and only small ``dl_*`` overlay
tables (delta adds + tombstones) ship per revision (engine/flat.py
DeltaMeta, engine/device.py _prepare_delta).  A check on the touched
edges must observe the new revision immediately (asserted every round).

Metrics: delta re-index latency (host materialize + device overlay) and
sustained updates/sec, at a base graph scaled by ``--edges`` (the full
config is 1B edges on v5e-16; one chip holds the 100M-class slice).

Multi-host status: ShardedEngine.prepare(prev=...) also advances
incrementally — bucket-sharded base tables stay resident per shard and
the delta-sized overlay ships replicated
(parallel/sharded.py _prepare_delta_sharded, tested on the CPU mesh in
test_delta_level.py) — so the per-revision device cost is O(delta) on
one chip AND on a mesh.  The remaining O(E) cost per revision is the
HOST-side column merge in apply_delta."""

import argparse
import time

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from benchmarks.common import start_backend, emit, note

SCHEMA = """
definition user {}
definition team { relation member: user }
definition repo {
    relation maintainer: user | team#member
    relation reader: user
    permission read = reader + maintainer
}
"""

EPOCH = 1_700_000_000_000_000


def build_base(n_edges: int):
    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner
    from gochugaru_tpu.store.snapshot import build_snapshot_from_columns

    cs = compile_schema(parse_schema(SCHEMA))
    interner = Interner()
    rng = np.random.default_rng(17)
    n_users = 100_000
    n_teams = 1000
    n_repos = max(n_edges // 20, 1000)
    users = np.array([interner.node("user", f"u{i}") for i in range(n_users)], np.int64)
    teams = np.array([interner.node("team", f"t{i}") for i in range(n_teams)], np.int64)
    repos = np.array([interner.node("repo", f"r{i}") for i in range(n_repos)], np.int64)
    slot = cs.slot_of_name

    n_member = n_teams * 50
    n_maint = n_repos
    n_reader = n_edges - n_member - n_maint
    res = np.concatenate([
        np.repeat(teams, 50), repos, rng.choice(repos, n_reader),
    ])
    rel = np.concatenate([
        np.full(n_member, slot["member"], np.int64),
        np.full(n_maint, slot["maintainer"], np.int64),
        np.full(n_reader, slot["reader"], np.int64),
    ])
    subj = np.concatenate([
        rng.choice(users, n_member),
        rng.choice(teams, n_maint),
        rng.choice(users, n_reader),
    ])
    srel = np.concatenate([
        np.full(n_member, -1, np.int64),
        np.full(n_maint, slot["member"], np.int64),
        np.full(n_reader, -1, np.int64),
    ])
    snap = build_snapshot_from_columns(
        1, cs, interner,
        res=res, rel=rel, subj=subj, srel=srel, epoch_us=EPOCH,
    )
    return cs, snap, interner, slot, users, repos


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edges", type=int, default=10_000_000)
    ap.add_argument("--delta", type=int, default=1000)
    ap.add_argument("--rounds", type=int, default=10)
    # chain-growth warmup: the dl_* overlay tables step shapes in 4×
    # bands as the accumulated delta grows (16k → 65k → 262k → 1M rows;
    # each step retraces the chain kernel once, ~1s).  At --delta 1000
    # on a 10M-edge base the chain runs ~1250 revisions to compaction,
    # so those ~8 retraces amortize to <10 ms/rev — the measured window
    # starts past the dense early crossings to report the rate the
    # other ~95% of the chain sees (the excluded cost is printed)
    ap.add_argument("--warmup", type=int, default=20)
    args = ap.parse_args()
    note(f"platform={start_backend()}")

    from gochugaru_tpu import rel as relmod
    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.store.delta import apply_delta

    cs, snap, interner, slot, users, repos = build_base(args.edges)
    note(f"base edges={snap.num_edges}")
    engine = DeviceEngine(cs)
    dsnap = engine.prepare(snap)

    rng = np.random.default_rng(5)
    lat_mat, lat_overlay, lat_probe = [], [], []
    warm_ms = 0.0
    incremental = 0
    for rnd in range(args.warmup + args.rounds):
        adds = [
            relmod.must_from_triple(
                f"repo:r{rng.integers(0, 1000)}", "reader",
                f"user:fresh_{rnd}_{i}",
            )
            for i in range(args.delta)
        ]
        deletes = []
        t0 = time.perf_counter()
        snap = apply_delta(snap, snap.revision + 1, adds, deletes, interner=interner)
        t1 = time.perf_counter()
        dsnap = engine.prepare(snap, prev=dsnap)
        t_ov = time.perf_counter()
        if dsnap.flat_meta is not None and dsnap.flat_meta.delta is not None:
            incremental += 1
        # freshness probe: a just-added edge must be visible at the new
        # revision
        probe = relmod.must_from_triple(
            f"{adds[0].resource_type}:{adds[0].resource_id}",
            "read",
            f"{adds[0].subject_type}:{adds[0].subject_id}",
        )
        d, p, ovf = engine.check_batch(dsnap, [probe], now_us=EPOCH)
        t2 = time.perf_counter()
        assert bool(d[0]), "freshness probe failed: delta not visible"
        if rnd < args.warmup:
            warm_ms += (t2 - t0) * 1000
            continue
        lat_mat.append((t1 - t0) * 1000)
        lat_overlay.append((t_ov - t1) * 1000)
        lat_probe.append((t2 - t_ov) * 1000)

    # --warmup 0 keeps the old behavior of dropping the first sample
    # (it carries the one-time kernel trace); an empty window is an error
    drop = 1 if args.warmup == 0 and len(lat_mat) > 1 else 0
    mat = np.asarray(lat_mat[drop:])
    overlay = np.asarray(lat_overlay[drop:])
    probe_t = np.asarray(lat_probe[drop:])
    if mat.size == 0:
        raise SystemExit("no measured rounds: raise --rounds")
    total_ms = mat.mean() + overlay.mean() + probe_t.mean()
    rate = args.delta / (total_ms / 1000)
    # the per-stage breakdown rides ON the row (not just a stderr note)
    # so the 100M-edge (config 5b) run's in-suite vs solo spread is
    # decomposable from the recorded JSON: materialize is host column
    # merging (memory-pressure-sensitive), overlay is the device delta
    # prepare, probe is the freshness check dispatch
    emit("watch_reindex_updates_per_sec", rate, "updates/sec", rate / 1_000_000,
         edges=int(args.edges), batch=int(args.delta),
         materialize_ms=round(float(mat.mean()), 2),
         overlay_ms=round(float(overlay.mean()), 2),
         probe_ms=round(float(probe_t.mean()), 2))
    note(
        f"delta={args.delta} materialize={mat.mean():.1f}ms "
        f"device-overlay={overlay.mean():.1f}ms probe={probe_t.mean():.1f}ms "
        f"total={total_ms:.1f}ms/delta "
        f"incremental={incremental}/{args.warmup + args.rounds} rounds; "
        f"warmup ({args.warmup} revs incl. chain-growth retraces) "
        f"{warm_ms:.0f}ms total, excluded"
    )

    # folded-check throughput BETWEEN deltas: this schema's `read` folds
    # (union of relation leaves), and round-5 incremental maintenance
    # keeps the fold armed across the chain (engine/fold.py
    # fold_delta_update) — so steady-state checks on the delta-chained
    # snapshot must run at fold speed, not walked speed
    import jax
    import jax.numpy as jnp

    meta = dsnap.flat_meta
    fold_armed = bool(meta is not None and meta.fold_pairs)
    dm = meta.delta if meta is not None else None
    note(
        f"fold armed={fold_armed} delta_level={dm is not None} "
        f"pf_dirty={bool(dm and dm.pf_dirty)} "
        f"pf_ovl_e={bool(dm and dm.pf_ovl_e)}"
    )
    B = 131_072
    qr = rng.choice(repos, B).astype(np.int32)
    qp = np.full(B, slot["read"], np.int32)
    qs = rng.choice(users, B).astype(np.int32)
    queries, qctx = engine._columns_preamble(
        dsnap, qr, qp, qs, None, None, None, None
    )
    got = engine.flat_fn_and_args(
        dsnap, queries, qctx, jnp.int32(snap.now_rel32(EPOCH)), B
    )
    if got is not None:
        fn, fargs = got
        jax.block_until_ready(fn(*fargs))
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(4):
                out = fn(*fargs)
            jax.block_until_ready(out)
            best = max(best, 4 * B / (time.perf_counter() - t0))
        emit(
            "watch_folded_check_throughput", best, "checks/sec/chip",
            best / 10_000_000, edges=int(args.edges), batch=B,
        )


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
