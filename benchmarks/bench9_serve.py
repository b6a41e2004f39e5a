"""Open-loop serving bench: Poisson traffic through the continuous
batcher vs the closed-loop pinned-tier rate.

Every other bench hands the engine pre-formed batches (closed loop: the
next batch waits for the last — the load adapts to the server, hiding
queueing).  This one is OPEN loop, the honest serving methodology:
submissions arrive on a Poisson process at a FIXED offered load whether
or not the server keeps up, subjects are zipf-skewed, and each
submission is a small CheckMany (the reference's request shape).  The
micro-batch former (gochugaru_tpu/serve/) coalesces them onto the
pinned tier ladder; we report goodput, shed rate, the batch-occupancy
histogram, and queue+service p50/p99 per offered-load step — so the
headline reads "N concurrent clients at p99 ≤ Y ms", not batch
throughput.

Since round 19 the file also measures the revision-pinned verdict
cache + in-flight dedup (engine/vcache.py, `with_serving(cache=True)`
at min_latency).  The SWEEP stays cache-off — byte-for-byte the
pre-cache serving path, so the committed serve_openloop_goodput
trajectory remains apples-to-apples — and the cache rides alongside:
a cache-on companion row at the top offered load (``cache="on"``, with
``cache_hit_rate`` / ``dedup_frac`` / ``unique_frac`` columns), plus
two same-run A/Bs: ``serve_cache_ab`` (the headline — blocking
request-path checks over zipf-hot tuples, where a cache hit skips the
evaluator round trip a blocking caller waits out) and
``serve_cache_openloop_ab`` (open-loop saturation through the serving
handle — on the 1-core proxy wall-clock is ~parity because the
front-end shares the core and the kernel already overlaps host Python;
what collapses is device rows dispatched per answered check, and the
goodput multiplier belongs to silicon).  The cache win is an in-file
A/B, not a cross-round comparison.

Honesty rules: the closed-loop denominator is measured in THIS process
at the serving tier; latencies are per-submission submit→resolve times
from the futures themselves (no waiting threads in the hot path);
oracle parity is sampled on real coalesced answers — INCLUDING
cache-served ones; zero retraces is asserted from the latency.compiles
counter across the whole sweep (single-slot tier shapes are pre-pinned:
a cache-shrunk residual batch can be read-only or admin-only).

One JSON line per load step ("serve_openloop_sweep") plus the headline
("serve_openloop_goodput") at the highest load whose queue+service p99
stays within 3x the quiet-window small-batch p99.
"""

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPOCH_US = 1_700_000_000_000_000


def build_store_world(client, n_repos, n_users, n_orgs, edges, rng):
    """GitHub-RBAC-shaped world imported columnarly through the client
    (the serving handle needs a store-backed snapshot chain)."""
    import numpy as np

    from gochugaru_tpu.utils.context import background

    ctx = background()
    client.write_schema(ctx, """
    definition user {}
    definition org { relation admin: user  relation member: user }
    definition repo {
        relation org: org
        relation reader: user
        permission admin = org->admin
        permission read = reader + admin + org->member
    }
    """)
    ru = rng.integers(0, n_users, edges)
    rr = rng.integers(0, n_repos, edges)
    client.import_relationship_columns(
        ctx, resource_type="repo",
        resource_ids=[f"r{i}" for i in rr], resource_relation="reader",
        subject_type="user", subject_ids=[f"u{i}" for i in ru],
    )
    client.import_relationship_columns(
        ctx, resource_type="repo",
        resource_ids=[f"r{i}" for i in range(n_repos)],
        resource_relation="org", subject_type="org",
        subject_ids=[f"o{i % n_orgs}" for i in range(n_repos)],
    )
    client.import_relationship_columns(
        ctx, resource_type="org",
        resource_ids=[f"o{i}" for i in range(n_orgs)],
        resource_relation="admin", subject_type="user",
        subject_ids=[f"u{i % n_users}" for i in range(n_orgs)],
    )
    mu = rng.integers(0, n_users, n_orgs * 4)
    client.import_relationship_columns(
        ctx, resource_type="org",
        resource_ids=[f"o{i % n_orgs}" for i in range(n_orgs * 4)],
        resource_relation="member", subject_type="user",
        subject_ids=[f"u{i}" for i in mu],
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--edges", type=int, default=200_000)
    ap.add_argument("--repos", type=int, default=20_000)
    ap.add_argument("--users", type=int, default=5_000)
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="measurement window per offered-load step")
    ap.add_argument("--loads", default="0.5,0.8,0.9",
                    help="offered load as fractions of the closed-loop rate")
    ap.add_argument("--submit", type=int, default=64,
                    help="checks per submission (CheckMany size)")
    ap.add_argument("--clients", type=int, default=32,
                    help="distinct fairness client ids in the arrival stream")
    ap.add_argument("--zipf", type=float, default=1.2,
                    help="zipf exponent for subject skew")
    ap.add_argument("--oracle-samples", type=int, default=50,
                    help="coalesced submissions re-checked on the host oracle")
    ap.add_argument("--no-cache", action="store_true",
                    help="skip the verdict-cache companion row and A/Bs"
                         " (the sweep itself is always cache-off — the"
                         " pre-round-19 bench byte-for-byte)")
    ap.add_argument("--no-ab", action="store_true",
                    help="skip the cache on/off saturation A/B")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        args.edges = min(args.edges, 50_000)
        args.repos = min(args.repos, 5_000)
        args.seconds = min(args.seconds, 2.0)

    from benchmarks.common import (
        NORTH_STAR_RATE,
        emit,
        start_backend,
        note,
        small_batch_latency,
    )

    platform = start_backend()
    import gc

    import numpy as np

    from gochugaru_tpu import consistency
    from gochugaru_tpu.client import new_tpu_evaluator, with_latency_mode
    from gochugaru_tpu.serve import ServeConfig
    from gochugaru_tpu.utils import metrics as _metrics
    from gochugaru_tpu.utils import perf as _perf
    from gochugaru_tpu.utils.context import background
    from gochugaru_tpu.utils.errors import ShedError

    rng = np.random.default_rng(5)
    c = new_tpu_evaluator(with_latency_mode())
    t0 = time.perf_counter()
    build_store_world(c, args.repos, args.users, 16, args.edges, rng)
    cs = consistency.full()
    ctx = background()
    snap = c.store.snapshot_for(cs)
    engine = c._engine_for(snap)
    dsnap = c._dsnap_for(engine, snap)
    note(f"world: edges={snap.num_edges} built in"
         f" {time.perf_counter() - t0:.1f}s platform={platform}")

    # -- interned query pools (zipf-skewed subjects) ---------------------
    inter = snap.interner
    slot = snap.compiled.slot_of_name
    repo_ids = np.array(
        [inter.node("repo", f"r{i}") for i in range(args.repos)], np.int32
    )
    user_ids = np.array(
        [inter.node("user", f"u{i}") for i in range(args.users)], np.int32
    )
    POOL = 1 << 18
    zipf_users = (rng.zipf(args.zipf, POOL) - 1) % args.users
    pool_res = repo_ids[rng.integers(0, args.repos, POOL)]
    pool_subj = user_ids[zipf_users]
    pool_perm = np.where(
        rng.random(POOL) < 0.9, slot["read"], slot["admin"]
    ).astype(np.int32)

    # -- closed-loop pinned-tier denominator + quiet-window p99 ----------
    TIER = 1024
    lp = engine.latency_path(dsnap)
    q = (pool_res[:TIER], pool_perm[:TIER].copy(), pool_subj[:TIER])
    q[1][:] = slot["read"]  # one slot set → one pinned kernel, like serving
    for _ in range(5):
        lp.dispatch_columns(*q, now_us=EPOCH_US)
    reps = 60 if args.quick else 150
    t0 = time.perf_counter()
    for i in range(reps):
        lp.dispatch_columns(
            np.roll(q[0], i), q[1], np.roll(q[2], 2 * i), now_us=EPOCH_US
        )
    closed_rate = reps * TIER / (time.perf_counter() - t0)
    quiet = small_batch_latency(
        engine, dsnap, q[0], q[1], q[2], now_us=EPOCH_US,
        warmup=10, reps=120 if args.quick else 300,
    )
    quiet_p99_ms = quiet["p99_ms"]
    note(f"closed-loop tier-{TIER} rate {closed_rate:,.0f} checks/s;"
         f" quiet-window p99 {quiet_p99_ms} ms")

    # single-slot tier pins: the verdict cache shrinks a formed batch to
    # its unique misses, so a residual dispatch can be read-only or
    # admin-only at any tier — pin those (slot-subset, tier) shapes up
    # front so the zero-retrace assertion measures serving, not warmup
    for tier in (256, 1024, 4096):
        for sv in (slot["read"], slot["admin"]):
            qq = (pool_res[:tier], np.full(tier, sv, np.int32),
                  pool_subj[:tier])
            for _ in range(2):
                lp.dispatch_columns(*qq, now_us=EPOCH_US)

    m = _metrics.default
    cache_on = not args.no_cache
    scfg = ServeConfig(hold_max_s=0.001)
    scfg_off = ServeConfig(hold_max_s=0.001, dedup=False)

    # -- shared step machinery -------------------------------------------
    def warm_burst(handle, n, pace_s):
        """Pin every (slot-subset, tier) executable the sweep will form:
        a rapid-fire burst fills the TOP tiers, a paced trickle forms
        the small ones.  The zero-retrace assertion then covers the
        MEASURED window, the standard warm-serving discipline."""
        futs = []
        for k in range(n):
            s = int(rng.integers(0, POOL - args.submit))
            while True:
                try:
                    futs.append(handle.submit_columns(
                        ctx, pool_res[s:s + args.submit],
                        pool_perm[s:s + args.submit],
                        pool_subj[s:s + args.submit],
                        client_id=k % args.clients,
                    ))
                    break
                except ShedError:  # warm as fast as admission allows
                    time.sleep(0.005)
            if pace_s:
                time.sleep(pace_s)
        for f in futs:
            f.result(timeout=60.0)

    def cache_columns(delta, done_checks):
        hits = delta("cache.hits")
        misses = delta("cache.misses")
        uniq = delta("serve.unique_checks")
        dup = delta("serve.dedup_parked") + delta("dedup.batch_dups")
        return dict(
            cache_hit_rate=round(hits / (hits + misses), 4)
            if (hits + misses) else 0.0,
            dedup_frac=round(dup / done_checks, 4) if done_checks else 0.0,
            unique_frac=round(uniq / done_checks, 4)
            if (done_checks and uniq) else 1.0,
        )

    def run_load_step(handle, frac, offered):
        """One paced open-loop step at a fixed offered load; returns the
        row dict (including the wall-time ledger block)."""
        sub_rate = offered / args.submit
        n_subs = max(int(sub_rate * args.seconds), 16)
        gaps = rng.exponential(1.0 / sub_rate, n_subs)
        arrivals = np.cumsum(gaps)
        starts = rng.integers(0, POOL - args.submit, n_subs)
        client_ids = rng.integers(0, args.clients, n_subs)

        base0 = m.snapshot()
        futures = []
        sheds = 0
        depth_samples = []
        stop_sampler = threading.Event()

        def sampler():
            while not stop_sampler.is_set():
                depth_samples.append(m.gauge("serve.queue_depth"))
                time.sleep(0.005)

        st = threading.Thread(target=sampler, daemon=True)
        st.start()
        gc.collect()
        gc.disable()
        # closed wall-time ledger: the step's whole window accounts
        # into form/queue-wait/host-prep/H2D/kernel/D2H/filter/idle
        # buckets (utils/perf.py) — the 21× queue-vs-quiet question
        # becomes columns on the row block below
        ledger = _perf.WallLedger().start()
        t_start = time.perf_counter()
        for k in range(n_subs):
            target = t_start + arrivals[k]
            slack = target - time.perf_counter()
            if slack > 0.0015:
                # coarse pacing: sleep off the bulk, let sub-ms
                # arrivals micro-burst (Poisson in aggregate) —
                # spinning per arrival would burn the core the
                # dispatcher needs
                time.sleep(slack - 0.001)
            s = starts[k]
            try:
                futures.append(handle.submit_columns(
                    ctx,
                    pool_res[s:s + args.submit],
                    pool_perm[s:s + args.submit],
                    pool_subj[s:s + args.submit],
                    client_id=int(client_ids[k]),
                ))
            except ShedError:  # open-loop counts sheds, not retries;
                sheds += 1     # any other failure must FAIL the row
                futures.append(None)
        # drain
        deadline = time.perf_counter() + 30.0
        for f in futures:
            if f is not None:
                f.result(timeout=max(deadline - time.perf_counter(), 0.1))
        t_end = time.perf_counter()
        wall = ledger.stop()
        gc.enable()
        stop_sampler.set()
        st.join(timeout=1.0)

        lat_ms = np.array([
            (f.t_done - f.t_submit) * 1000.0
            for f in futures if f is not None
        ])
        snap_m = m.snapshot()

        def delta(key):
            return snap_m.get(key, 0) - base0.get(key, 0)

        done_checks = delta("serve.checks")
        elapsed = t_end - t_start
        goodput = done_checks / elapsed
        batches = max(delta("serve.batches"), 1)
        occ_n = delta("serve.occupancy.count")
        occ_mean = (
            delta("serve.occupancy.sum") / occ_n if occ_n else 0.0
        )
        ds = np.asarray(depth_samples) if depth_samples else np.zeros(1)
        row = dict(
            load_frac=frac,
            offered=round(offered, 1),
            goodput=round(goodput, 1),
            goodput_vs_closed=round(goodput / closed_rate, 4),
            submissions=n_subs,
            shed_rate=round(sheds / n_subs, 4),
            p50_ms=round(float(np.percentile(lat_ms, 50)), 3),
            p99_ms=round(float(np.percentile(lat_ms, 99)), 3),
            batches=int(batches),
            mean_batch=round(done_checks / batches, 1),
            occupancy_mean=round(occ_mean, 4),
            flush_full=int(delta("serve.flush_full")),
            flush_deadline=int(delta("serve.flush_deadline")),
            flush_maxhold=int(delta("serve.flush_maxhold")),
            queue_depth_p50=round(float(np.percentile(ds, 50)), 1),
            queue_depth_max=int(ds.max()),
            device_dispatches=int(delta("latency.dispatches")),
            **cache_columns(delta, done_checks),
        )
        row["wall"] = wall
        return row

    def saturation_run(handle, seconds):
        """Open-loop capacity arm of the cache A/B: submit flat-out for
        a fixed wall window with future-based backpressure (a shed
        waits on the oldest in-flight submission — real queue pressure,
        no guessed sleeps; both arms run the SAME code), drain, and
        report goodput."""
        from collections import deque

        base0 = m.snapshot()
        outstanding = deque()
        lat_ms = []
        gc.collect()
        gc.disable()
        t_start = time.perf_counter()
        t_stop = t_start + seconds
        k = 0
        while time.perf_counter() < t_stop:
            s = int(rng.integers(0, POOL - args.submit))
            try:
                outstanding.append(handle.submit_columns(
                    ctx, pool_res[s:s + args.submit],
                    pool_perm[s:s + args.submit],
                    pool_subj[s:s + args.submit],
                    client_id=k % args.clients,
                ))
                k += 1
            except ShedError:
                if outstanding:
                    f = outstanding.popleft()
                    f.result(timeout=60.0)
                    lat_ms.append((f.t_done - f.t_submit) * 1000.0)
                continue
            if len(outstanding) >= 256:
                f = outstanding.popleft()
                f.result(timeout=60.0)
                lat_ms.append((f.t_done - f.t_submit) * 1000.0)
        while outstanding:
            f = outstanding.popleft()
            f.result(timeout=60.0)
            lat_ms.append((f.t_done - f.t_submit) * 1000.0)
        t_end = time.perf_counter()
        gc.enable()
        snap_m = m.snapshot()

        def delta(key):
            return snap_m.get(key, 0) - base0.get(key, 0)

        done_checks = delta("serve.checks")
        la = np.asarray(lat_ms) if lat_ms else np.zeros(1)
        return dict(
            goodput=round(done_checks / (t_end - t_start), 1),
            checks=int(done_checks),
            p50_ms=round(float(np.percentile(la, 50)), 3),
            p99_ms=round(float(np.percentile(la, 99)), 3),
            device_dispatches=int(delta("latency.dispatches")),
            **cache_columns(delta, done_checks),
        )

    def request_path_run(client, seconds, threads, hot):
        """Blocking per-request arm of the cache A/B: ``threads``
        closed-loop callers hammer ``client.check`` (min_latency) over
        zipf-hot tuples — the reference's interactive shape, where a
        repeated read answered from a revision-pinned verdict skips the
        whole evaluator round trip (nothing overlaps a blocking call,
        so the win is wall-clock, not just device occupancy)."""
        base0 = m.snapshot()
        done = [0] * threads
        stop = time.perf_counter() + seconds

        def worker(w):
            lr = np.random.default_rng(977 + w)
            n = 0
            while time.perf_counter() < stop:
                qs = [hot[(lr.zipf(args.zipf) - 1) % len(hot)]
                      for _ in range(4)]
                client.check(ctx, serve_cs, *qs)
                n += 4
            done[w] = n

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        el = time.perf_counter() - t0
        snap_m = m.snapshot()

        def delta(key):
            return snap_m.get(key, 0) - base0.get(key, 0)

        return dict(
            goodput=round(sum(done) / el, 1),
            checks=int(sum(done)),
            **cache_columns(delta, sum(done)),
        )

    def emit_sweep_row(row, cache_label, metric="serve_openloop_sweep"):
        # the cache-on companion emits under its OWN metric name:
        # bench_compare keys on the newest line per name, and the
        # companion must not shadow the sweep's trajectory row
        emit(
            metric, row["goodput"], "checks/sec",
            row["goodput"] / NORTH_STAR_RATE,
            edges=int(snap.num_edges), batch=args.submit,
            cache=cache_label,
            **{k: v for k, v in row.items() if k != "wall"},
        )

    # -- open-loop sweep: CACHE-OFF, byte-for-byte the pre-cache serving
    # path (cs=full, raw former, direct evaluate) — the committed
    # serve_openloop_goodput trajectory stays an apples-to-apples
    # comparison across rounds; the cache rows ride alongside below
    loads = [float(x) for x in args.loads.split(",")]
    serve_cs = consistency.min_latency()
    rows = []
    on_row = None
    handle = c.with_serving(cs=cs, config=scfg_off, cache=False)
    warm_burst(handle, 400, 0.0)   # saturates → full 4096-tier batches
    warm_burst(handle, 48, 0.003)  # trickle → 256/1024-tier batches
    compiles_sweep0 = m.counter("latency.compiles")
    # serving GC discipline: collections pause every thread and land
    # straight in the tail; collect between steps instead (the futures
    # are acyclic — nothing leaks while disabled)
    try:
        for frac in loads:
            row = run_load_step(handle, frac, frac * closed_rate)
            wall = row["wall"]
            rows.append(row)
            note(
                f"load {frac:.2f}: offered {row['offered']:,.0f} → goodput"
                f" {row['goodput']:,.0f} checks/s"
                f" ({row['goodput'] / closed_rate:.0%} of closed)"
                f" p50 {row['p50_ms']}ms p99 {row['p99_ms']}ms"
                f" shed {row['shed_rate']:.1%} mean batch"
                f" {row['mean_batch']:.0f} depth_max {row['queue_depth_max']}"
                f" hit_rate {row['cache_hit_rate']:.1%}"
                f" dedup {row['dedup_frac']:.1%}"
            )
            note(
                "wall ledger: " + " ".join(
                    f"{b}={wall['fracs'][b]:.1%}"
                    for b in (*_perf.WALL_BUCKETS, "idle")
                    if wall["fracs"][b] > 0
                ) + f" closure={wall['closure_frac']:.1%}"
            )
            emit_sweep_row(row, "off")
            # the wall-time row block: one line per load step, every
            # bucket a column.  Closure holds by construction (idle is
            # the residual), so the teeth are elsewhere: zero dropped
            # intervals and the device stages actually reported.  A
            # fully cache-resident step may legitimately dispatch
            # nothing — the kernel tooth only bites when the device ran
            assert wall["closure_frac"] >= 0.95, wall
            assert wall["dropped"] == 0, wall
            if row["device_dispatches"] > 0:
                assert wall["seconds"]["kernel"] > 0, wall
            emit(
                "serve_wall_ledger", wall["closure_frac"], "frac",
                wall["closure_frac"],
                load_frac=frac, window_s=wall["window_s"],
                named_frac=wall["named_frac"],
                **{f"{b}_frac": wall["fracs"][b]
                   for b in (*_perf.WALL_BUCKETS, "idle")},
                **{f"{b}_s": wall["seconds"][b]
                   for b in (*_perf.WALL_BUCKETS, "idle")},
                intervals=wall["intervals"],
            )

        retraces = int(m.counter("latency.compiles") - compiles_sweep0)

        # -- cache+dedup companion row ------------------------------------
        # (same offered load as the sweep's FIRST row — sub-saturation,
        # so the row measures warm steady state and its promoted p99
        # stays a stable trajectory guard; the saturation behavior is
        # the open-loop A/B's job below)
        if cache_on:
            h_on = c.with_serving(cs=serve_cs, config=scfg, cache=True)
            try:
                warm_burst(h_on, 120 if args.quick else 400, 0.0)
                # cover the whole query pool once so the row measures
                # the warm steady state, not the cache-fill transient
                futs = []
                for s0 in range(0, POOL - args.submit, args.submit):
                    while True:
                        try:
                            futs.append(h_on.submit_columns(
                                ctx, pool_res[s0:s0 + args.submit],
                                pool_perm[s0:s0 + args.submit],
                                pool_subj[s0:s0 + args.submit],
                                client_id=s0 % args.clients,
                            ))
                            break
                        except ShedError:
                            time.sleep(0.002)
                for f in futs:
                    f.result(timeout=120.0)
                on_row = run_load_step(
                    h_on, loads[0], loads[0] * closed_rate
                )
            finally:
                h_on.close()
            emit_sweep_row(on_row, "on", metric="serve_openloop_cache_on")
            note(
                f"cache-on row @ load {loads[0]:.2f}: goodput"
                f" {on_row['goodput']:,.0f} checks/s p50"
                f" {on_row['p50_ms']}ms p99 {on_row['p99_ms']}ms hit_rate"
                f" {on_row['cache_hit_rate']:.1%} unique_frac"
                f" {on_row['unique_frac']:.2%}"
            )

        # -- cache on/off A/B ---------------------------------------------
        # Two arms, two truths.  (1) REQUEST PATH (the headline): for a
        # blocking caller nothing overlaps the evaluator round trip, so
        # a cache hit is a wall-clock win — the reference's "repeated
        # read answered from a revision-pinned result".  (2) OPEN-LOOP
        # capacity through the serving handle: on the 1-core proxy the
        # submission front-end shares the core with dispatch and the
        # device kernel already overlaps host Python, so removing
        # device work cannot raise goodput here — the honest outcome is
        # ~parity wall-clock with a collapse in device rows dispatched
        # per answered check (device_dispatches, unique_frac); the
        # goodput multiplier belongs to silicon, where the device is
        # the bottleneck (same split PR-10 documented for p99)
        ab = None
        ab_open = None
        if cache_on and not args.no_ab:
            from gochugaru_tpu import rel as _rel
            from gochugaru_tpu.client import (
                new_tpu_evaluator as _new, with_store as _wstore,
                with_latency_mode as _wlat, with_verdict_cache as _wvc,
            )

            ab_s = min(args.seconds, 2.0) if args.quick else args.seconds
            hot = [
                _rel.must_from_triple(
                    f"repo:r{rng.integers(args.repos)}", "read",
                    f"user:u{rng.integers(args.users)}",
                )
                for _ in range(4096)
            ]
            # symmetric fresh clients over the SAME store (`c` carries
            # the sweep's cache — it must not serve the off arm; fresh
            # engines warm identically, so neither arm rides the
            # other's pins)
            c_req_on = _new(_wlat(), _wvc(), _wstore(c.store))
            c_req_off = _new(_wlat(), _wstore(c.store))
            thr = 4 if args.quick else 8
            request_path_run(c_req_off, min(ab_s, 1.0), 2, hot)  # warm
            request_path_run(c_req_on, min(ab_s, 1.0), 2, hot)   # warm
            req_off = request_path_run(c_req_off, ab_s, thr, hot)
            req_on = request_path_run(c_req_on, ab_s, thr, hot)
            # parity: cached answers must equal the uncached evaluator's
            sample = hot[:256]
            got_on = c_req_on.check(ctx, serve_cs, *sample)
            got_off = c_req_off.check(ctx, serve_cs, *sample)
            req_match = got_on == got_off
            speedup = round(req_on["goodput"] / req_off["goodput"], 3)
            ab = dict(on=req_on, off=req_off, speedup=speedup,
                      match=req_match, threads=thr)
            note(
                f"cache A/B (request path, {thr} blocking threads,"
                f" {ab_s:.1f}s/arm): off {req_off['goodput']:,.0f} → on"
                f" {req_on['goodput']:,.0f} checks/s = {speedup}x,"
                f" hit_rate {req_on['cache_hit_rate']:.1%},"
                f" parity={req_match}"
            )
            open_off = saturation_run(handle, ab_s)  # the OFF sweep handle
            h_on2 = c.with_serving(cs=serve_cs, config=scfg, cache=True)
            try:
                saturation_run(h_on2, min(ab_s, 1.0))  # cache warm-up
                open_on = saturation_run(h_on2, ab_s)
            finally:
                h_on2.close()
            ab_open = dict(
                on=open_on, off=open_off,
                speedup=round(open_on["goodput"] / open_off["goodput"], 3),
            )
            note(
                f"cache A/B (open-loop saturation, {ab_s:.1f}s/arm): off"
                f" {open_off['goodput']:,.0f} → on"
                f" {open_on['goodput']:,.0f} checks/s"
                f" = {ab_open['speedup']}x wall-clock (front-end-bound"
                " on the 1-core proxy); device dispatches"
                f" {open_off['device_dispatches']} → "
                f"{open_on['device_dispatches']}, hit_rate"
                f" {open_on['cache_hit_rate']:.1%}"
            )

        # -- oracle parity on sampled coalesced answers -------------------
        # Two passes over the SAME sample offsets: the cache-off sweep
        # handle (the pre-PR check) and a cache-armed handle whose
        # cache is warm from the companion/A-B runs — so oracle_match
        # genuinely covers CACHE-SERVED coalesced answers, not just the
        # direct path
        oracle = c._oracle_for(snap)
        ns = args.oracle_samples
        oracle_match = True
        si = rng.integers(0, POOL - 4, ns)
        h_par = (
            c.with_serving(cs=serve_cs, config=scfg, cache=True)
            if cache_on else None
        )
        try:
            for s in si:
                want = np.fromiter(
                    (c._check_interned(oracle, snap, pool_res[s + j],
                                       pool_perm[s + j], pool_subj[s + j])
                     for j in range(4)),
                    bool, count=4,
                )
                # h_par twice: the second round is a guaranteed cache
                # HIT at the same revision — parity covers the hit path
                for hh in (handle, h_par, h_par):
                    if hh is None:
                        continue
                    got = np.asarray(hh.check_columns(
                        ctx, pool_res[s:s + 4], pool_perm[s:s + 4],
                        pool_subj[s:s + 4],
                    ))
                    if not (got == want).all():
                        oracle_match = False
                        note(f"ORACLE MISMATCH at pool offset {s}"
                             f" (cache={'on' if hh is h_par else 'off'})")
        finally:
            if h_par is not None:
                h_par.close()
    finally:
        handle.close()

    if ab is not None:
        emit(
            "serve_cache_ab", ab["speedup"], "x", ab["speedup"],
            edges=int(snap.num_edges), surface="request_path",
            threads=ab["threads"],
            goodput_on=ab["on"]["goodput"], goodput_off=ab["off"]["goodput"],
            hit_rate=ab["on"]["cache_hit_rate"],
            parity=bool(ab["match"]),
            oracle_match=bool(oracle_match),
            zipf=args.zipf, platform=platform,
            note=(
                "same-run A/B, blocking client.check at min_latency over"
                " zipf-hot tuples: a cache hit skips the evaluator round"
                " trip a blocking caller otherwise waits out; off ="
                " pre-cache path byte-for-byte"
            ),
        )
    if ab_open is not None:
        emit(
            "serve_cache_openloop_ab", ab_open["speedup"], "x",
            ab_open["speedup"],
            edges=int(snap.num_edges), batch=args.submit,
            goodput_on=ab_open["on"]["goodput"],
            goodput_off=ab_open["off"]["goodput"],
            p99_on_ms=ab_open["on"]["p99_ms"],
            p99_off_ms=ab_open["off"]["p99_ms"],
            device_dispatches_on=ab_open["on"]["device_dispatches"],
            device_dispatches_off=ab_open["off"]["device_dispatches"],
            hit_rate=ab_open["on"]["cache_hit_rate"],
            dedup_frac=ab_open["on"]["dedup_frac"],
            unique_frac=ab_open["on"]["unique_frac"],
            zipf=args.zipf, platform=platform,
            note=(
                "open-loop saturation through the serving handle: on the"
                " 1-core proxy the front-end shares the core and the"
                " kernel already overlaps host Python, so wall-clock is"
                " ~parity while device rows dispatched per answered"
                " check collapse — the goodput multiplier belongs to"
                " silicon, where the device is the bottleneck"
            ),
        )

    # -- headline: the highest load whose p99 holds the 3x bar; when no
    # row holds it (the 1-core CPU proxy shares the dispatch core with
    # the submission front-end, so queueing starts well below the
    # device's own capacity), the best sustained-goodput row with a
    # sub-2% shed rate carries the headline and p99_bar_met says so
    bar_ms = 3.0 * quiet_p99_ms
    ok_rows = [r for r in rows if r["p99_ms"] <= bar_ms and
               r["shed_rate"] < 0.01]
    if ok_rows:
        head = max(ok_rows, key=lambda r: r["goodput"])
    else:
        sustained = [r for r in rows if r["shed_rate"] < 0.02] or rows
        head = max(sustained, key=lambda r: r["goodput"])
    hw = head["wall"]
    emit(
        "serve_openloop_goodput", head["goodput"], "checks/sec",
        head["goodput"] / NORTH_STAR_RATE,
        edges=int(snap.num_edges), batch=args.submit,
        closed_rate=round(closed_rate, 1),
        goodput_vs_closed=head["goodput_vs_closed"],
        load_frac=head["load_frac"],
        p50_ms=head["p50_ms"], p99_ms=head["p99_ms"],
        quiet_p99_ms=quiet_p99_ms,
        p99_vs_quiet=round(head["p99_ms"] / max(quiet_p99_ms, 1e-9), 3),
        p99_bar_met=bool(ok_rows),
        shed_rate=head["shed_rate"],
        clients=args.clients, zipf=args.zipf,
        oracle_match=bool(oracle_match),
        retraces=retraces,
        queue_depth_p50=head["queue_depth_p50"],
        queue_depth_max=head["queue_depth_max"],
        # verdict-cache companions (the headline row itself is the
        # cache-OFF trajectory row; the cache-on numbers ride as
        # columns so the comparison lives in one emitted line)
        cache="off",
        cache_speedup=None if ab is None else ab["speedup"],
        cache_openloop_speedup=None if ab_open is None
        else ab_open["speedup"],
        cache_hit_rate=None if on_row is None else on_row["cache_hit_rate"],
        dedup_frac=None if on_row is None else on_row["dedup_frac"],
        unique_frac=None if on_row is None else on_row["unique_frac"],
        cache_on_load_frac=None if on_row is None else on_row["load_frac"],
        cache_on_goodput=None if on_row is None else on_row["goodput"],
        cache_on_p50_ms=None if on_row is None else on_row["p50_ms"],
        cache_on_p99_ms=None if on_row is None else on_row["p99_ms"],
        # measured-roofline columns (perf ledger: gathered bytes/check ×
        # goodput against the triad-microbench ceiling) + the headline
        # step's wall-time split — the 21× explanation as columns: on
        # the 1-core proxy host-side buckets dominate the window while
        # the kernel share stays small, which is exactly "queueing
        # starts below device capacity because the host core is shared"
        **_perf.roofline_columns(head["goodput"], dsnap=dsnap),
        wall_closure_frac=hw["closure_frac"],
        wall_kernel_frac=hw["fracs"]["kernel"],
        wall_host_frac=round(
            hw["fracs"]["host_prep"] + hw["fracs"]["filter"]
            + hw["fracs"]["form"] + hw["fracs"]["h2d"] + hw["fracs"]["d2h"],
            4,
        ),
        wall_queue_frac=hw["fracs"]["queue_wait"],
        wall_idle_frac=hw["fracs"]["idle"],
        pad_fraction=_perf.pad_stats()["pad_fraction"],
        platform=platform,
        note=(
            f"{args.clients} concurrent clients at p99 <="
            f" {head['p99_ms']} ms: open-loop Poisson arrivals,"
            f" zipf({args.zipf}) subjects, {args.submit}-check"
            " submissions coalesced onto the pinned tier ladder"
            " (cache-off trajectory row; cache_on_* columns carry the"
            " verdict-cache companion)"
        ),
    )
    assert retraces == 0, f"{retraces} retraces across the sweep"
    assert oracle_match, "coalesced answers diverged from the host oracle"
    if ab is not None:
        assert ab["match"], "cached request-path answers diverged"
        assert ab["speedup"] >= 1.3, (
            f"cache request-path speedup {ab['speedup']} < 1.3x"
        )
    return 0


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
