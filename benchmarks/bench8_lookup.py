"""Bulk reverse reachability: LookupResources as masked frontier SpMV,
measured at the config-3 world (1M docs / 10M edges, 5-hop nested
groups + folder trees — benchmarks/bench3_docs.py's generator).

Three honest columns, separated on purpose:

- ``lookup_candidates_per_s`` — candidate resources/second through the
  device frontier expansion (engine/spmv.py over the reverse-CSR
  tables) for BULK subjects (group usersets viewing near-root folders:
  the ~1M-resource answers this surface exists for), TRUE-rate basis:
  total candidates divided by the median wall clock of full sequential
  drains — no pipelining, no per-subject best-of.  The bar is ≥1M/chip
  (vs_baseline's denominator here).  ``mixed_rate`` on the same row is
  the rate over 48 RANDOM users — small-reach lookups are dominated by
  the fixed per-hop dispatch cost (a ~1k-resource answer cannot
  amortize it), so the two numbers are kept separate instead of
  averaged into something misleading.
- ``lookup_first_result_latency`` — wall time to the FIRST page (1k
  results) of a cursored lookup, the streaming claim: answers start
  flowing before the fixpoint completes (measured on random users AND
  on a bulk subject whose full answer takes ~100x longer).
- ``lookup_full_answer_throughput`` — results/second for the complete
  bulk answer, INCLUDING the exact forward filter — what an
  export-everything caller sees.

``oracle_match`` on the headline row asserts the frontier answer equals
the host walker's (engine/lookup.py — the superseded O(E log E)
transposed-index path, kept as the parity oracle) for measured
subjects; the walker's index build time rides along as
``walker_index_build_s`` for contrast.

Every lookup row also carries ``device_dispatches`` — the number of
device program launches the measured phase actually made (read from the
``lookup.dispatches`` + ``spmm.dispatches`` counters, engine/spmv.py and
engine/spmm.py), so dispatch-floor claims are data, not prose.  The
``lookup_fused_vs_looped`` A/B row runs the SAME mixed-user sample
through the fused K-hop SpMM path (``EngineConfig.spmm`` on, one pinned
dispatch per lookup) and the looped per-hop path (off) on the SAME
prepared snapshot, promoting ``mixed_users_rate`` (higher-better) and
``dispatches_per_lookup`` (lower-better) for the trajectory guard.
"""

import time

import numpy as np

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from benchmarks.common import (
    emit,
    join_lookup_prewarm,
    start_backend,
    note,
)

#: the acceptance bar: candidate resources per second per chip
CANDIDATE_RATE_BAR = 1_000_000


def main() -> None:
    note(f"platform={start_backend()}")
    from benchmarks.bench3_docs import EPOCH, build_world
    from gochugaru_tpu.engine import lookup as lm
    from gochugaru_tpu.engine import spmv
    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.engine.oracle import SnapshotOracle

    t0 = time.perf_counter()
    cs, snap, users, docs, slot = build_world()
    note(f"edges={snap.num_edges} nodes={snap.num_nodes} "
         f"worldgen={time.perf_counter()-t0:.0f}s")
    engine = DeviceEngine(cs)
    t0 = time.perf_counter()
    dsnap = engine.prepare(snap)
    join_lookup_prewarm()
    note(f"prepare={time.perf_counter()-t0:.0f}s "
         f"has_rev={dsnap.flat_meta.has_rev}")
    assert spmv.frontier_ok(engine, dsnap), "frontier path must serve"
    oracle = SnapshotOracle(snap, {})
    interner = snap.interner

    from gochugaru_tpu.utils.metrics import default as _mt

    def _disp() -> float:
        return _mt.counter("lookup.dispatches") + _mt.counter(
            "spmm.dispatches"
        )

    rng = np.random.default_rng(11)
    sample = [int(u) for u in rng.choice(users, 48, replace=False)]
    st = spmv.state_for(engine, dsnap)
    rtid = interner.type_lookup("document")
    member = cs.slot_of_name["member"]
    viewer = cs.slot_of_name["viewer"]
    gtid = interner.type_lookup("group")

    def drain_candidates(u: int, srel: int = -1, state=st) -> int:
        n = 0
        for blk in state.resource_candidates(rtid, u, srel, -1, EPOCH):
            n += blk.shape[0]
        return n

    # bulk subjects: the groups viewing the lowest-index folders (near
    # the roots of the arity-16 forest) — their member usersets reach
    # whole subtrees, the bulk-reverse-reachability workload
    bulk: list = []
    fnodes = np.asarray(
        [interner.lookup("folder", f"f{i}") for i in range(64)], np.int64
    )
    for f in fnodes:
        m = (snap.e_res == f) & (snap.e_rel == viewer) & (snap.e_srel1 > 0)
        for g in snap.e_subj[m]:
            if snap.node_type[int(g)] == gtid and int(g) not in bulk:
                bulk.append(int(g))
    bulk = bulk[:6]
    assert bulk, "no group views a near-root folder in this world"

    # ---- candidate expansion TRUE rate ---------------------------------
    mixed_of = {u: drain_candidates(u) for u in sample}  # warm (compiles)
    bulk_of = {g: drain_candidates(g, member) for g in bulk}

    def timed(subjects, srel, state=st):
        """(median wall s, device dispatches per drain) over 3 reps."""
        reps = []
        d0 = _disp()
        for _ in range(3):
            t0 = time.perf_counter()
            for s in subjects:
                drain_candidates(s, srel, state)
            reps.append(time.perf_counter() - t0)
        per_drain = (_disp() - d0) / (3 * max(len(subjects), 1))
        return float(np.median(reps)), per_drain

    mixed_dt, mixed_dpl = timed(sample, -1)
    bulk_dt, bulk_dpl = timed(bulk, member)
    mixed_rate = sum(mixed_of.values()) / mixed_dt
    total_cands = sum(bulk_of.values())
    cand_rate = total_cands / bulk_dt
    heavy = max(bulk, key=lambda g: bulk_of[g])
    heavy_id = interner.key_of(heavy)[1]
    note(
        f"bulk expansion: {len(bulk)} userset subjects, {total_cands} "
        f"candidates in {bulk_dt*1000:.0f}ms → {cand_rate/1e6:.2f}M cand/s"
        f" (heaviest: {bulk_of[heavy]}, {bulk_dpl:.1f} dispatches/drain); "
        f"mixed 48 random users: {sum(mixed_of.values())} candidates → "
        f"{mixed_rate/1e6:.2f}M/s at {mixed_dpl:.1f} dispatches/lookup"
    )

    # ---- fused vs looped A/B: same snapshot, same sample ---------------
    # the looped state serves through a spmm=False engine over the SAME
    # prepared tables — the pre-PR per-hop path, byte-for-byte
    import dataclasses as _dc

    from gochugaru_tpu.engine.device import DeviceEngine as _DE

    eng_off = _DE(cs, _dc.replace(engine.config, spmm=False))
    st_off = spmv.FrontierState(eng_off, dsnap)
    looped_of = {u: drain_candidates(u, -1, st_off) for u in sample}  # warm
    assert looped_of == mixed_of, "fused/looped candidate counts differ"
    looped_dt, looped_dpl = timed(sample, -1, st_off)
    looped_rate = sum(looped_of.values()) / looped_dt
    note(
        f"fused-vs-looped A/B (48 mixed users): fused "
        f"{mixed_rate/1e6:.2f}M cand/s @ {mixed_dpl:.1f} disp/lookup, "
        f"looped {looped_rate/1e6:.2f}M @ {looped_dpl:.1f} — "
        f"{mixed_rate/max(looped_rate,1e-9):.1f}x"
    )

    # ---- first-result latency (cursored page 1) ------------------------
    def first_page_ms(node: int, stype: str, srel: str) -> float:
        sid = interner.key_of(node)[1]
        # a fresh stream per timing: drop the continuation cache entry
        dsnap.__dict__.pop("_lookup_streams", None)
        t0 = time.perf_counter()
        lm.lookup_resources_page(
            engine, dsnap, "document", "view", stype, sid, srel,
            page_size=1_000, now_us=EPOCH,
            oracle_factory=lambda: oracle,
        )
        return (time.perf_counter() - t0) * 1000

    fp_d0 = _disp()
    fr = [first_page_ms(u, "user", "") for u in sample[:16]]
    fr_p50 = float(np.percentile(fr, 50))
    heavy_first = first_page_ms(heavy, "group", "member")
    fp_disp = _disp() - fp_d0

    # ---- full bulk answer (exact filter included) ----------------------
    fa_d0 = _disp()
    t0 = time.perf_counter()
    full = lm.lookup_resources_device(
        engine, dsnap, "document", "view", "group", heavy_id, "member",
        now_us=EPOCH, oracle_factory=lambda: oracle,
    )
    full_dt = time.perf_counter() - t0
    full_rate = len(full) / max(full_dt, 1e-9)
    fa_disp = _disp() - fa_d0

    # ---- oracle parity vs the host walker ------------------------------
    t0 = time.perf_counter()
    match = True
    checks = [("group", interner.key_of(heavy)[1], "member")] + [
        ("user", interner.key_of(u)[1], "") for u in sample[:4]
    ]
    for stype, sid, srel in checks:
        names = ("document", "view", stype, sid, srel)
        resolved = lm._resolve_resources(dsnap, *names)
        if resolved is None:
            continue
        _rt, _p, srel_slot, subj_node, wc_node = resolved
        seen = lm._walk_resource_candidates(snap, subj_node, srel_slot,
                                            wc_node)
        wcand = seen[snap.node_type[seen] == rtid]
        filt, id_of = lm._res_filter(
            engine, dsnap, resolved, names, EPOCH, lambda: oracle,
        )
        walker_ids = sorted(id_of(int(g)) for g in filt(wcand))
        got = lm.lookup_resources_device(
            engine, dsnap, *names[:2], *names[2:],
            now_us=EPOCH, oracle_factory=lambda: oracle,
        )
        if got != walker_ids:
            match = False
            note(f"PARITY MISMATCH for {stype}:{sid}: "
                 f"{len(got)} vs walker {len(walker_ids)}")
    walker_s = time.perf_counter() - t0
    note(f"walker parity pass (incl. one-time transposed-index build): "
         f"{walker_s:.0f}s oracle_match={match}")

    emit(
        "lookup_candidates_per_s", cand_rate, "candidates/sec/chip",
        cand_rate / CANDIDATE_RATE_BAR,
        edges=int(snap.num_edges), batch=len(bulk),
        oracle_match=bool(match),
        total_candidates=int(total_cands),
        heavy_candidates=int(bulk_of[heavy]),
        mixed_rate=round(mixed_rate, 1),
        mixed_users_rate=round(mixed_rate, 1),
        mixed_candidates=int(sum(mixed_of.values())),
        device_dispatches=round(bulk_dpl * len(bulk), 1),
        dispatches_per_lookup=round(mixed_dpl, 2),
        hops=int(_mt.counter("lookup.hops")),
        note=f"bar {CANDIDATE_RATE_BAR/1e6:.0f}M cand/s; bulk userset "
             "subjects, TRUE-rate (sequential drains, median of 3); "
             "mixed_users_rate = 48 random users; device_dispatches = "
             "per bulk rep",
    )
    emit(
        "lookup_fused_vs_looped", mixed_rate / max(looped_rate, 1e-9), "x",
        mixed_rate / max(looped_rate, 1e-9),
        edges=int(snap.num_edges), batch=len(sample),
        oracle_match=bool(match),
        mixed_users_rate=round(mixed_rate, 1),
        looped_mixed_users_rate=round(looped_rate, 1),
        dispatches_per_lookup=round(mixed_dpl, 2),
        looped_dispatches_per_lookup=round(looped_dpl, 2),
        device_dispatches=round(mixed_dpl * len(sample), 1),
        note="same snapshot, same 48 mixed users: fused K-hop SpMM "
             "(EngineConfig.spmm on) vs looped per-hop SpMV (off); "
             "value = fused/looped candidate-rate ratio",
    )
    emit(
        "lookup_first_result_latency", fr_p50, "ms", 2.0 / max(fr_p50, 1e-9),
        edges=int(snap.num_edges), batch=1_000,
        bulk_first_ms=round(heavy_first, 1),
        bulk_full_ms=round(full_dt * 1000, 1),
        device_dispatches=int(fp_disp),
        note="time to first 1k-result page (cursored stream); bulk_* = "
             "the heavy userset subject",
    )
    emit(
        "lookup_full_answer_throughput", full_rate, "results/sec/chip",
        full_rate / CANDIDATE_RATE_BAR,
        edges=int(snap.num_edges), batch=len(full),
        full_answer_ms=round(full_dt * 1000, 1),
        walker_index_build_s=round(walker_s, 1),
        device_dispatches=int(fa_disp),
        note="heaviest bulk subject, exact forward filter included",
    )


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
