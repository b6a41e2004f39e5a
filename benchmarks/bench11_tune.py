"""Self-tuning A/B: the offline tuner's proposed config vs static
presets on a mixed workload.

The tuner (gochugaru_tpu/tune/) closes the loop from the perf ledger to
EngineConfig: profile a workload under the default preset, capture one
telemetry snapshot (per-tier occupancy histograms, flush reasons,
dedup fractions, pad waste), ``propose()`` a config diff with predicted
deltas and per-knob measured evidence, ``apply_diff()``, and re-run.
This bench is the honesty check on that loop, in three parts:

1. **Mixed-workload sweep** — three profiles (interactive small-batch
   zipf arrivals, bulk CheckMany, lookup-heavy) each run under every
   static preset AND under the tuned config, scored on goodput×p99
   (score = goodput / p99_ms).  The tuned config must beat every
   preset on ≥2 of 3 profiles and regress none beyond tolerance —
   self-tuning that wins one workload by sacrificing another is a
   preset, not a tuner.
2. **Prediction audit** — for each applied knob whose predicted delta
   is measurable in this run (pad-waste for the tier ladder, p99 for
   the hold deadline), the measured delta must land within 2× of the
   prediction; both numbers ride the emitted JSON so the trajectory
   shows prediction quality, not just outcomes.
3. **Contract checks** — the tuned ladder is typically NON-pow2 (the
   occupancy rule quantizes to 64-lane multiples): zero
   ``latency.retraces`` across all arms and bitwise oracle parity on
   sampled coalesced answers prove the tuned ladder keeps the pinned
   no-retrace and correctness contracts.

Headline: ``tuned_vs_best_preset_goodput`` — the geometric mean over
profiles of tuned goodput vs the best static preset's goodput, with
``pad_waste_frac`` (tuned arm, lower-better) and the per-knob
prediction table as columns.
"""

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPOCH_US = 1_700_000_000_000_000


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edges", type=int, default=60_000)
    ap.add_argument("--repos", type=int, default=6_000)
    ap.add_argument("--users", type=int, default=2_000)
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="interactive-profile window per arm")
    ap.add_argument("--rate", type=float, default=120.0,
                    help="interactive offered load, submissions/s"
                         " (sub-saturation on the 1-core proxy: p99 must"
                         " measure config, not queue depth)")
    ap.add_argument("--submit", type=int, default=9,
                    help="checks per interactive submission")
    ap.add_argument("--bulk-submit", type=int, default=300,
                    help="checks per bulk CheckMany submission")
    ap.add_argument("--bulk-rate", type=float, default=70.0,
                    help="bulk offered load, submissions/s (70×300 ="
                         " 21k checks/s keeps the proxy below"
                         " saturation so p99 measures config, not"
                         " queue growth)")
    ap.add_argument("--reps", type=int, default=2,
                    help="scored repetitions per (arm, profile); the"
                         " best rep by score counts — sheds one-off"
                         " ambient stalls on a shared-CPU proxy")
    ap.add_argument("--bulk-reps", type=int, default=120)
    ap.add_argument("--lookups", type=int, default=120)
    ap.add_argument("--zipf", type=float, default=1.2)
    ap.add_argument("--oracle-samples", type=int, default=40)
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="max allowed score regression on any profile")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        args.edges = min(args.edges, 30_000)
        args.repos = min(args.repos, 3_000)
        args.seconds = min(args.seconds, 1.2)
        args.bulk_reps = min(args.bulk_reps, 60)
        args.lookups = min(args.lookups, 90)

    from benchmarks.bench9_serve import build_store_world
    from benchmarks.common import emit, start_backend, note

    platform = start_backend()
    import numpy as np

    from gochugaru_tpu import consistency
    from gochugaru_tpu.client import (
        new_tpu_evaluator,
        with_engine_config,
        with_latency_mode,
        with_store,
    )
    from gochugaru_tpu.engine.plan import EngineConfig
    from gochugaru_tpu.serve import ServeConfig
    from gochugaru_tpu.tune import TuneTarget, apply_diff, collect_snapshot, propose
    from gochugaru_tpu.utils import metrics as _metrics
    from gochugaru_tpu.utils.context import background
    from gochugaru_tpu.utils.errors import ShedError

    m = _metrics.default
    rng = np.random.default_rng(11)
    ctx = background()
    builder = new_tpu_evaluator(with_latency_mode())
    t0 = time.perf_counter()
    build_store_world(builder, args.repos, args.users, 8, args.edges, rng)
    store = builder.store
    cs = consistency.full()
    snap = store.snapshot_for(cs)
    note(f"world: edges={snap.num_edges} built in"
         f" {time.perf_counter() - t0:.1f}s platform={platform}")

    inter = snap.interner
    slot = snap.compiled.slot_of_name
    repo_ids = np.array(
        [inter.node("repo", f"r{i}") for i in range(args.repos)], np.int32
    )
    user_ids = np.array(
        [inter.node("user", f"u{i}") for i in range(args.users)], np.int32
    )
    POOL = 1 << 16
    zipf_users = (rng.zipf(args.zipf, POOL) - 1) % args.users
    pool_res = repo_ids[rng.integers(0, args.repos, POOL)]
    pool_subj = user_ids[zipf_users]
    pool_perm = np.where(
        rng.random(POOL) < 0.9, slot["read"], slot["admin"]
    ).astype(np.int32)

    # -- the arms --------------------------------------------------------
    # presets: the shipped default, a latency-biased preset, and a
    # throughput-biased preset — the static configs an operator would
    # plausibly pick without measurements
    DEFAULT_E = EngineConfig()
    PRESETS = {
        "default": (DEFAULT_E, ServeConfig()),
        "lowlat": (DEFAULT_E, ServeConfig(hold_max_s=0.001)),
        "bulk": (DEFAULT_E, ServeConfig(hold_max_s=0.004)),
    }

    def submit_span(h, s, n, client_id=0):
        while True:
            try:
                return h.submit_columns(
                    ctx, pool_res[s:s + n], pool_perm[s:s + n],
                    pool_subj[s:s + n], client_id=client_id,
                )
            except ShedError:
                time.sleep(0.002)

    # fixed per-profile schedules, drawn ONCE and replayed identically
    # by every arm — the A/B is paired, so arm deltas measure config,
    # not workload draw
    n_inter = max(int(args.rate * args.seconds), 32)
    SCHED_INTER = (
        np.cumsum(rng.exponential(1.0 / args.rate, n_inter)),
        rng.integers(0, POOL - args.submit, n_inter),
    )
    SCHED_BULK = (
        np.cumsum(rng.exponential(1.0 / args.bulk_rate, args.bulk_reps)),
        rng.integers(0, POOL - args.bulk_submit, args.bulk_reps),
    )
    LOOKUP_USERS = [
        int((rng.zipf(args.zipf) - 1) % args.users)
        for _ in range(args.lookups)
    ]

    def paced_run(h, sched, n_checks):
        """Open-loop Poisson arrivals from a fixed schedule of
        ``n_checks``-check submissions; per-submission latency from the
        futures themselves.  Both check profiles share this shape so
        their p99 measures config (hold wait + padded-dispatch cost),
        not the arrival discipline.  The first 10% of submissions are
        the profile's own warm transient and excluded from the stats;
        GC is off during the window (collections land in the tail)."""
        import gc

        arrivals, starts = sched
        n_subs = len(starts)
        futs = []
        base = m.snapshot()
        gc.collect()
        gc.disable()
        t_start = time.perf_counter()
        try:
            for k in range(n_subs):
                slack = t_start + arrivals[k] - time.perf_counter()
                if slack > 0.0015:
                    time.sleep(slack - 0.001)
                futs.append(submit_span(h, int(starts[k]), n_checks,
                                        client_id=k % 8))
            for f in futs:
                f.result(timeout=60.0)
        finally:
            gc.enable()
        el = time.perf_counter() - t_start
        trim = max(3, n_subs // 10)
        lat = np.array([(f.t_done - f.t_submit) * 1000.0
                        for f in futs[trim:]])
        done = m.snapshot().get("serve.checks", 0) - base.get("serve.checks", 0)
        return dict(
            goodput=round(done / el, 1),
            p50_ms=round(float(np.percentile(lat, 50)), 3),
            p99_ms=round(float(np.percentile(lat, 99)), 3),
        )

    def profile_interactive(h):
        return paced_run(h, SCHED_INTER, args.submit)

    def profile_bulk(h):
        return paced_run(h, SCHED_BULK, args.bulk_submit)

    def profile_lookup(c):
        """Lookup-heavy: cursored LookupResources pages for the FIXED
        zipf-hot subject sequence (identical across arms); goodput is
        resources returned per second."""
        import gc

        lat = []
        total = 0
        gc.collect()
        gc.disable()
        t_start = time.perf_counter()
        try:
            for u in LOOKUP_USERS:
                t0 = time.perf_counter()
                page = c.lookup_resources_page(
                    ctx, cs, "repo#read", f"user:u{u}", page_size=256
                )
                lat.append((time.perf_counter() - t0) * 1000.0)
                total += len(page.ids)
        finally:
            gc.enable()
        el = time.perf_counter() - t_start
        la = np.asarray(lat[max(2, len(lat) // 10):])
        return dict(
            goodput=round(total / el, 1),
            p50_ms=round(float(np.percentile(la, 50)), 3),
            p99_ms=round(float(np.percentile(la, 99)), 3),
        )

    oracle_failures = []

    def oracle_sample(c, h, snap_a):
        oracle = c._oracle_for(snap_a)
        for s in rng.integers(0, POOL - 4, args.oracle_samples):
            want = np.fromiter(
                (c._check_interned(oracle, snap_a, pool_res[s + j],
                                   pool_perm[s + j], pool_subj[s + j])
                 for j in range(4)),
                bool, count=4,
            )
            got = np.asarray(h.check_columns(
                ctx, pool_res[s:s + 4], pool_perm[s:s + 4],
                pool_subj[s:s + 4],
            ))
            if not (got == want).all():
                oracle_failures.append(int(s))

    def build_arm(ecfg, scfg):
        """Fresh client over the shared store + serving handle, every
        tier pin warmed SEQUENTIALLY (one submission sized to the tier
        itself — submitting several sizes at once lets the hold window
        coalesce them into a single top-tier batch, leaving lower pins
        cold so a profile dispatch pays the XLA compile
        mid-measurement)."""
        c = new_tpu_evaluator(
            with_latency_mode(), with_engine_config(ecfg), with_store(store)
        )
        h = c.with_serving(cs=cs, config=scfg, cache=False)
        for _ in range(2):
            for t in ecfg.latency_tiers:
                n = min(int(t), POOL - 1)
                submit_span(h, 0, n).result(timeout=120.0)
        c.lookup_resources_page(ctx, cs, "repo#read", "user:u0",
                                page_size=256)
        return c, h

    # -- 1. profiling pass: the default preset feeds the tuner ----------
    note("profiling pass (default preset) for the tuner")
    c0, h0 = build_arm(*PRESETS["default"])
    try:
        profile_interactive(h0)
        profile_bulk(h0)
        profile_lookup(c0)
    finally:
        h0.close()

    tsnap = collect_snapshot(
        m, engine_config=PRESETS["default"][0],
        serve_config=PRESETS["default"][1],
    )
    target = TuneTarget(engine=PRESETS["default"][0],
                        serve=PRESETS["default"][1], cache_bytes=None)
    occ_dbg = {
        t: dict(n=o["count"], mean=round(o["sum"] / max(o["count"], 1), 1))
        for t, o in sorted(tsnap["occupancy"].items(), key=lambda kv: int(kv[0]))
    }
    note(f"snapshot: flush={tsnap['flush']} occupancy={occ_dbg}")
    diff = propose(tsnap, target)
    note("tuner proposal:")
    for line in diff.render().splitlines():
        note("  " + line)
    tuned = apply_diff(target, diff)
    tuned_tiers = tuned.engine.latency_tiers
    nonpow2 = [t for t in tuned_tiers if t & (t - 1)]
    note(f"tuned ladder {tuned_tiers} (non-pow2 tiers: {nonpow2 or 'none'})"
         f" hold {tuned.serve.hold_max_s} dedup {tuned.serve.dedup}")

    # -- 2. scored pass: all arms interleaved profile-major -------------
    # Arms run back-to-back within each profile (and the whole sweep
    # repeats ``--reps`` times, best rep by score counting) so ambient
    # drift on a shared-CPU proxy lands on every arm alike instead of
    # on whichever arm happened to run last.
    ARMS = dict(PRESETS)
    ARMS["tuned"] = (tuned.engine, tuned.serve)
    arm_objs = {}
    for name, (ecfg, scfg) in ARMS.items():
        arm_objs[name] = build_arm(ecfg, scfg)
    pad_acc = {name: [0.0, 0.0] for name in ARMS}
    results = {name: {} for name in ARMS}

    def scored(p, r):
        # lookup is CLOSED-loop: its goodput and latency are one
        # measurement, so dividing one by the other double-counts the
        # same noise — goodput alone is the score there.  The check
        # profiles are open-loop (goodput pinned by the schedule) so
        # goodput×(1/p99) rewards meeting load at low tail.
        if p == "lookup":
            return r["goodput"]
        return r["goodput"] / max(r["p99_ms"], 1e-6)

    PROFILE_FNS = (
        ("interactive", profile_interactive, True),
        ("bulk", profile_bulk, True),
        ("lookup", profile_lookup, False),
    )
    arm_order = list(arm_objs.items())
    for rep in range(max(1, args.reps)):
        # alternate arm order so positional bias (allocator state, LLC
        # residency, ambient load ramps) lands on every arm alike
        order = arm_order if rep % 2 == 0 else arm_order[::-1]
        for p, fn, takes_handle in PROFILE_FNS:
            for name, (c, h) in order:
                l0 = m.counter("perf.pad.live_lanes")
                t0 = m.counter("perf.pad.total_lanes")
                r = fn(h if takes_handle else c)
                pad_acc[name][0] += m.counter("perf.pad.live_lanes") - l0
                pad_acc[name][1] += m.counter("perf.pad.total_lanes") - t0
                best = results[name].get(p)
                if best is None or scored(p, r) > scored(p, best):
                    results[name][p] = r

    snap_a = store.snapshot_for(cs)
    for name, (c, h) in arm_objs.items():
        oracle_sample(c, h, snap_a)
        h.close()
    for name in ARMS:
        dl, dt = pad_acc[name]
        results[name]["pad_waste_frac"] = (
            round(1.0 - dl / dt, 4) if dt else 0.0
        )
        for p, r in sorted(results[name].items()):
            if isinstance(r, dict):
                note(f"  [{name}/{p}] goodput {r['goodput']:,.0f}/s"
                     f" p50 {r['p50_ms']}ms p99 {r['p99_ms']}ms")
        note(f"  [{name}] pad_waste_frac {results[name]['pad_waste_frac']}")

    retraces = int(m.counter("latency.retraces"))
    oracle_match = not oracle_failures

    # -- 3. score: goodput×p99 per profile, tuned vs best preset --------
    PROFILES = ("interactive", "bulk", "lookup")

    def score(arm, p):
        return scored(p, results[arm][p])

    wins = 0
    regressions = []
    ratios = []
    per_profile = {}
    for p in PROFILES:
        best_preset = max(PRESETS, key=lambda a: score(a, p))
        ts, bs = score("tuned", p), score(best_preset, p)
        beat_all = all(ts > score(a, p) for a in PRESETS)
        wins += beat_all
        gp_ratio = (results["tuned"][p]["goodput"]
                    / results[best_preset][p]["goodput"])
        ratios.append(gp_ratio)
        if ts < (1.0 - args.tolerance) * bs:
            regressions.append(p)
        per_profile[p] = dict(
            best_preset=best_preset,
            tuned_score=round(ts, 2), best_score=round(bs, 2),
            score_ratio=round(ts / bs, 3),
            goodput_ratio=round(gp_ratio, 3),
            tuned_beats_all=bool(beat_all),
        )
        note(f"profile {p}: tuned score {ts:,.1f} vs best preset"
             f" '{best_preset}' {bs:,.1f} ({ts / bs:.2f}x),"
             f" beats_all={beat_all}")
    geomean_goodput = math.exp(sum(math.log(r) for r in ratios) / len(ratios))

    # -- 4. prediction audit: measured delta within 2x of predicted -----
    def within_2x(predicted, measured, floor):
        return abs(measured - predicted) <= max(abs(predicted), floor)

    predictions = []
    kd = diff.get("latency_tiers")
    if kd is not None and "pad_waste_frac" in kd.predicted:
        pred = kd.predicted["pad_waste_frac"]
        meas = (results["tuned"]["pad_waste_frac"]
                - results["default"]["pad_waste_frac"])
        predictions.append(dict(
            knob="latency_tiers", key="pad_waste_frac",
            predicted=round(pred, 4), measured=round(meas, 4),
            within_2x=bool(within_2x(pred, meas, 0.10)),
        ))
    kd = diff.get("hold_max_s")
    if kd is not None and "p99_ms" in kd.predicted:
        pred = kd.predicted["p99_ms"]
        meas = (results["tuned"]["interactive"]["p99_ms"]
                - results["default"]["interactive"]["p99_ms"])
        predictions.append(dict(
            knob="hold_max_s", key="p99_ms",
            predicted=round(pred, 3), measured=round(meas, 3),
            within_2x=bool(within_2x(pred, meas, 1.0)),
        ))
    for pr in predictions:
        note(f"prediction {pr['knob']}/{pr['key']}: predicted"
             f" {pr['predicted']} measured {pr['measured']}"
             f" within_2x={pr['within_2x']}")

    emit(
        "tuned_vs_best_preset_goodput", round(geomean_goodput, 4), "x",
        round(geomean_goodput, 4),
        edges=int(snap.num_edges),
        profiles_won=wins, profiles=len(PROFILES),
        regressions=regressions,
        per_profile=per_profile,
        knobs_applied=[k.knob for k in diff.knobs],
        tuned_tiers=list(tuned_tiers),
        nonpow2_tiers=[int(t) for t in nonpow2],
        tuned_hold_max_s=tuned.serve.hold_max_s,
        tuned_dedup=tuned.serve.dedup,
        pad_waste_frac=results["tuned"]["pad_waste_frac"],
        pad_waste_frac_default=results["default"]["pad_waste_frac"],
        predictions=predictions,
        oracle_match=bool(oracle_match),
        retraces=retraces,
        zipf=args.zipf, platform=platform,
        note=(
            "geomean over 3 profiles of tuned goodput vs the best static"
            " preset; tuner configured from the default arm's telemetry"
            " snapshot only (occupancy histograms, flush reasons, pad"
            " ledger) — no per-arm fitting"
        ),
    )
    emit(
        "tune_pad_waste_frac", results["tuned"]["pad_waste_frac"], "frac",
        results["tuned"]["pad_waste_frac"],
        default_arm=results["default"]["pad_waste_frac"],
        tuned_tiers=list(tuned_tiers), platform=platform,
        note="share of dispatched lanes carrying padding, tuned arm",
    )

    assert retraces == 0, f"{retraces} retraces across arms"
    assert oracle_match, f"oracle mismatches at offsets {oracle_failures[:5]}"
    assert diff, "the default preset on this workload must yield proposals"
    assert wins >= 2, (
        f"tuned config won only {wins}/3 profiles: {per_profile}"
    )
    assert not regressions, (
        f"tuned config regressed beyond {args.tolerance:.0%} on"
        f" {regressions}: {per_profile}"
    )
    bad = [p for p in predictions if not p["within_2x"]]
    assert not bad, f"predictions off by more than 2x: {bad}"
    return 0


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
