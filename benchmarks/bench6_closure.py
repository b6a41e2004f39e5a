"""Incremental closure maintenance — member-edge write throughput.

Measures the write hot path this repo's ROADMAP called the top bail
class: membership-subgraph deltas (user ∈ team edges, nested team ∈ team
edges) used to force a full flattened-closure rebuild per revision; they
now advance the closure in O(Δ·depth) host work (store/closure.py
advance_closure) and reship only the O(closure) clx/ovfx tables, with
the fold staying armed (its pf_u side is closure-independent — the
reachability-pruned fold T-join of engine/fold.py fold_userset_rows).

Emits ``closure_update_throughput`` (updates/s over 30 measured rounds
at a --edges base) and asserts ``closure.rebuilds == 0`` across the
measured window — the acceptance bar for the incremental closure engine.
A freshness probe per round asserts the just-written membership is
immediately visible through a FOLDED permission (read = reader +
maintainer), i.e. the whole write→closure→check pipeline, not just the
host index.
"""

import argparse
import time

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from benchmarks.common import start_backend, emit, note

SCHEMA = """
definition user {}
definition team { relation member: user | team#member }
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
    rng = np.random.default_rng(19)
    n_users = 100_000
    n_teams = 1000
    n_repos = max(n_edges // 20, 1000)
    users = np.array([interner.node("user", f"u{i}") for i in range(n_users)], np.int64)
    teams = np.array([interner.node("team", f"t{i}") for i in range(n_teams)], np.int64)
    repos = np.array([interner.node("repo", f"r{i}") for i in range(n_repos)], np.int64)
    slot = cs.slot_of_name

    n_member = n_teams * 50
    # nesting: every 10th team also contains the next team's members —
    # member writes then propagate through pair-closure depth, not just
    # the seed level (the O(Δ·depth) term is real work)
    nest = np.arange(0, n_teams - 1, 10)
    n_maint = n_repos
    n_reader = n_edges - n_member - nest.shape[0] - n_maint
    res = np.concatenate([
        np.repeat(teams, 50), teams[nest], repos, rng.choice(repos, n_reader),
    ])
    rel_c = np.concatenate([
        np.full(n_member, slot["member"], np.int64),
        np.full(nest.shape[0], slot["member"], np.int64),
        np.full(n_maint, slot["maintainer"], np.int64),
        np.full(n_reader, slot["reader"], np.int64),
    ])
    subj = np.concatenate([
        rng.choice(users, n_member),
        teams[nest + 1],
        rng.choice(teams, n_maint),
        rng.choice(users, n_reader),
    ])
    srel = np.concatenate([
        np.full(n_member, -1, np.int64),
        np.full(nest.shape[0], slot["member"], np.int64),
        np.full(n_maint, slot["member"], np.int64),
        np.full(n_reader, -1, np.int64),
    ])
    snap = build_snapshot_from_columns(
        1, cs, interner,
        res=res, rel=rel_c, subj=subj, srel=srel, epoch_us=EPOCH,
    )
    return cs, snap, interner, slot, users, teams, repos


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edges", type=int, default=10_000_000)
    ap.add_argument("--delta", type=int, default=1000)
    ap.add_argument("--rounds", type=int, default=30)
    # chain-growth warmup, same rationale as bench5: dl_* shape-band
    # retraces and the one-time t_off flip happen in the first revisions
    ap.add_argument("--warmup", type=int, default=20)
    args = ap.parse_args()
    note(f"platform={start_backend()}")

    from gochugaru_tpu import rel as relmod
    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.store.delta import apply_delta
    from gochugaru_tpu.utils import metrics

    cs, snap, interner, slot, users, teams, repos = build_base(args.edges)
    note(f"base edges={snap.num_edges}")
    engine = DeviceEngine(cs)
    dsnap = engine.prepare(snap)
    if dsnap.closure_state is None:
        raise SystemExit("closure state missing: closure_delta disabled?")
    cl = dsnap.closure_state.st.cl
    note(f"closure pairs={cl.num_pairs} fold_armed="
         f"{bool(dsnap.flat_meta and dsnap.flat_meta.fold_pairs)}")

    rng = np.random.default_rng(11)
    lat_mat, lat_overlay, lat_probe = [], [], []
    warm_ms = 0.0
    incremental = 0
    rebuilds0 = applies0 = None
    live_adds = []  # adds from prior rounds, eligible for deletion
    for rnd in range(args.warmup + args.rounds):
        if rnd == args.warmup:
            rebuilds0 = metrics.default.counter("closure.rebuilds")
            applies0 = metrics.default.counter("closure.delta_applies")
        # half fresh member grants, half revocations of earlier grants —
        # adds AND deletes both exercise the advance (deletes are the
        # hard half: subset recompute, no derivation counting)
        n_del = min(len(live_adds), args.delta // 2)
        deletes = [live_adds.pop(rng.integers(0, len(live_adds)))
                   for _ in range(n_del)]
        adds = [
            relmod.must_from_triple(
                f"team:t{rng.integers(0, 1000)}", "member",
                f"user:u{rng.integers(0, 100_000)}",
            )
            for _ in range(args.delta - n_del)
        ]
        t0 = time.perf_counter()
        snap = apply_delta(snap, snap.revision + 1, adds, deletes,
                           interner=interner)
        t1 = time.perf_counter()
        dsnap = engine.prepare(snap, prev=dsnap)
        t_ov = time.perf_counter()
        if dsnap.flat_meta is not None and dsnap.flat_meta.delta is not None:
            incremental += 1
        # freshness probe THROUGH the folded permission: the new member
        # must read every repo their team maintains — pick one such repo
        probe_team = adds[0].resource_id
        probe = relmod.must_from_triple(
            f"team:{probe_team}", "member", f"user:{adds[0].subject_id}",
        )
        d, p, ovf = engine.check_batch(dsnap, [probe], now_us=EPOCH)
        t2 = time.perf_counter()
        assert bool(d[0]), "freshness probe failed: member delta not visible"
        live_adds.extend(adds)
        if rnd < args.warmup:
            warm_ms += (t2 - t0) * 1000
            continue
        lat_mat.append((t1 - t0) * 1000)
        lat_overlay.append((t_ov - t1) * 1000)
        lat_probe.append((t2 - t_ov) * 1000)

    rebuilds = metrics.default.counter("closure.rebuilds") - rebuilds0
    applies = metrics.default.counter("closure.delta_applies") - applies0
    mat = np.asarray(lat_mat)
    overlay = np.asarray(lat_overlay)
    probe_t = np.asarray(lat_probe)
    total_ms = mat.mean() + overlay.mean() + probe_t.mean()
    rate = args.delta / (total_ms / 1000)
    emit(
        "closure_update_throughput", rate, "updates/sec", rate / 1_000_000,
        edges=int(args.edges), batch=int(args.delta),
        rounds=int(args.rounds),
        rebuilds=int(rebuilds), delta_applies=int(applies),
        materialize_ms=round(float(mat.mean()), 2),
        overlay_ms=round(float(overlay.mean()), 2),
        probe_ms=round(float(probe_t.mean()), 2),
    )
    note(
        f"member-edge writes: delta={args.delta} "
        f"materialize={mat.mean():.1f}ms closure+overlay={overlay.mean():.1f}ms "
        f"probe={probe_t.mean():.1f}ms total={total_ms:.1f}ms/delta "
        f"incremental={incremental}/{args.warmup + args.rounds} "
        f"rebuilds={rebuilds:.0f} delta_applies={applies:.0f}; "
        f"warmup {warm_ms:.0f}ms total, excluded"
    )
    if rebuilds:
        raise SystemExit(
            f"acceptance violated: {rebuilds:.0f} closure rebuilds in the "
            f"measured window (must be 0)"
        )


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
