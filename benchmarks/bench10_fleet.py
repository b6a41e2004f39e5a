"""Fleet serving bench: replicated REPLICA PROCESSES behind the
consistent-hash router (gochugaru_tpu/fleet/).

Three phases, each a claim from the fleet round:

1. **Goodput scaling** — closed-loop callers through the router at
   min_latency against 1 replica, then against ``--replicas``.  On the
   1-core CPU proxy every replica process shares the same core with the
   router and the callers, so wall-clock scaling CANNOT reach the 2×
   bar physically — ``scaling_bar_met`` reports whether it did, and the
   row carries both arms so the trajectory is honest (the same
   discipline as PR-10's ``p99_bar_met``: measure, flag, don't
   fabricate).  The multiplier belongs to multi-core hosts, where
   replicas stop queueing on one another.

2. **Zero-stale parity** — per consistency strategy against the host
   oracle at the router store's head: full and at_least(zookie) rows
   must match the oracle exactly (quiesced min_latency too); then a
   DYNAMIC phase toggles one edge write-by-write and re-checks through
   the router with the freshly-minted zookie — read-your-writes on
   every toggle, counted as staleness violations if ever wrong.

3. **Failover** — a seeded mid-run SIGKILL of one replica process
   while full-consistency traffic flows.  Every in-window request must
   return exactly one correct answer (zero lost, zero duplicated, zero
   stale — the retry envelope reroutes through surviving replicas);
   the window p99 rides next to the quiet baseline p99 as
   ``failover_p99_ms``, the kill must be detected (ring eviction +
   ``fleet.failover`` incident bundle), and a restarted replica must
   bootstrap, catch up, and rejoin before the bench ends.

JSON lines: ``fleet_goodput_scaling`` (x, higher better),
``fleet_zero_stale`` (violations, lower better), ``failover_p99_ms``
(ms, lower better).
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spawn_replica(py, port, rid, env, stderr_path):
    """Start ``python -m gochugaru_tpu.fleet.replica`` and wait for its
    REPLICA-READY line; returns (Popen, host, port)."""
    import json

    proc = subprocess.Popen(
        [py, "-m", "gochugaru_tpu.fleet.replica",
         "--upstream", f"127.0.0.1:{port}", "--id", rid, "--host-only"],
        stdout=subprocess.PIPE, stderr=open(stderr_path, "w"),
        text=True, env=env,
    )
    deadline = time.monotonic() + 120.0
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("REPLICA-READY"):
            meta = json.loads(line.split(None, 1)[1])
            return proc, meta["host"], meta["port"]
        if not line and proc.poll() is not None:
            break
    tail = open(stderr_path).read()[-2000:]
    raise RuntimeError(f"replica {rid} never became ready: {tail}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--rels", type=int, default=20_000,
                    help="relationships in the bootstrap world")
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="per goodput arm")
    ap.add_argument("--clients", type=int, default=4,
                    help="closed-loop caller threads")
    ap.add_argument("--batch", type=int, default=16,
                    help="checks per router.check call")
    ap.add_argument("--toggles", type=int, default=40,
                    help="dynamic zero-stale write/check rounds")
    ap.add_argument("--failover-checks", type=int, default=200,
                    help="requests in the kill window")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.quick:
        args.rels = min(args.rels, 4_000)
        args.seconds = min(args.seconds, 2.0)
        args.toggles = min(args.toggles, 20)
        args.failover_checks = min(args.failover_checks, 100)

    from benchmarks.common import emit, start_backend, note

    platform = start_backend()

    import random
    from dataclasses import replace

    import numpy as np

    from gochugaru_tpu import consistency, rel
    from gochugaru_tpu.client import (
        new_tpu_evaluator, with_host_only_evaluation, with_store,
    )
    from gochugaru_tpu.fleet import FleetConfig, FleetRouter, zookie
    from gochugaru_tpu.utils import metrics as _metrics
    from gochugaru_tpu.utils import trace
    from gochugaru_tpu.utils.context import background

    m = _metrics.default
    rng = random.Random(20260806)
    cfg = replace(
        FleetConfig(),
        probe_interval_s=0.1,
        probe_timeout_s=1.0,
        heartbeat_s=0.1,
        freshness_wait_s=10.0,
        freshness_poll_s=0.02,
    )
    incident_dir = tempfile.mkdtemp(prefix="fleet-incidents-")
    rec = trace.install_recorder(trace.FlightRecorder(
        incident_dir=incident_dir, grace_s=0.0, cooldown_s=0.0,
    ))

    router = FleetRouter(config=cfg)
    ctx = background()
    router.write_schema(ctx, """
    definition user {}
    definition org { relation admin: user  relation member: user }
    definition repo {
        relation org: org
        relation reader: user
        permission admin = org->admin
        permission read = reader + admin + org->member
    }
    """)
    n_repos = max(args.rels // 4, 50)
    n_users = max(args.rels // 16, 20)
    t0 = time.perf_counter()
    CHUNK = 2000
    pending = rel.Txn()
    n_in = 0
    for i in range(args.rels):
        pending.touch(rel.must_from_triple(
            f"repo:r{rng.randrange(n_repos)}", "reader",
            f"user:u{rng.randrange(n_users)}",
        ))
        n_in += 1
        if n_in >= CHUNK:
            router.write(ctx, pending)
            pending, n_in = rel.Txn(), 0
    for i in range(n_repos):
        pending.touch(rel.must_from_triple(f"repo:r{i}", "org", f"org:o{i % 8}"))
    for o in range(8):
        pending.touch(rel.must_from_triple(f"org:o{o}", "admin", f"user:u{o}"))
        pending.touch(
            rel.must_from_triple(f"org:o{o}", "member", f"user:u{o + 9}")
        )
    router.write(ctx, pending)
    note(f"world: {args.rels} reader rels over {n_repos} repos built in"
         f" {time.perf_counter() - t0:.1f}s; head={router.head_revision};"
         f" platform={platform}")
    oracle = new_tpu_evaluator(
        with_store(router.store), with_host_only_evaluation()
    )

    # -- spawn replica processes -----------------------------------------
    env = dict(os.environ)
    if not platform.startswith("tpu"):
        env.setdefault("JAX_PLATFORMS", "cpu")
    py = sys.executable
    procs = {}
    t0 = time.perf_counter()
    for i in range(args.replicas):
        rid = f"r{i}"
        p, h, prt = spawn_replica(
            py, router.port, rid, env,
            os.path.join(incident_dir, f"{rid}.stderr"),
        )
        procs[rid] = (p, h, prt)
    note(f"{args.replicas} replica processes bootstrapped in"
         f" {time.perf_counter() - t0:.1f}s")

    def pool():
        qs = []
        for _ in range(4096):
            qs.append(rel.must_from_triple(
                f"repo:r{rng.randrange(n_repos)}", "read",
                f"user:u{rng.randrange(n_users)}",
            ))
        return qs

    POOL = pool()

    def goodput_arm(seconds):
        """Closed-loop callers through the router; returns checks/s."""
        stop = time.perf_counter() + seconds
        done = [0] * args.clients
        errs = []

        def worker(w):
            lr = random.Random(555 + w)
            n = 0
            while time.perf_counter() < stop:
                s = lr.randrange(len(POOL) - args.batch)
                try:
                    router.check(
                        background().with_timeout(30.0),
                        consistency.min_latency(),
                        *POOL[s:s + args.batch],
                    )
                    n += args.batch
                except BaseException as e:  # any loss fails the arm
                    errs.append(repr(e))
                    break
            done[w] = n

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(args.clients)]
        t_start = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        el = time.perf_counter() - t_start
        if errs:
            raise RuntimeError(f"goodput arm surfaced: {errs[:2]}")
        return sum(done) / el

    try:
        # -- phase 1: goodput, 1 replica vs N ---------------------------
        r0 = procs["r0"]
        router.add_replica(r0[1], r0[2], wait_ready_s=60.0)
        goodput_1 = goodput_arm(args.seconds)
        note(f"goodput @ 1 replica: {goodput_1:,.0f} checks/s")
        for rid in list(procs)[1:]:
            _, h, prt = procs[rid]
            router.add_replica(h, prt, wait_ready_s=60.0)
        goodput_n = goodput_arm(args.seconds)
        scaling = goodput_n / max(goodput_1, 1e-9)
        ncores = os.cpu_count() or 1
        bar_met = scaling >= 2.0
        note(f"goodput @ {args.replicas} replicas: {goodput_n:,.0f} checks/s"
             f" = {scaling:.2f}x (host has {ncores} core(s);"
             f" scaling_bar_met={bar_met})")
        emit(
            "fleet_goodput_scaling", round(scaling, 3), "x",
            round(scaling / 2.0, 4),
            replicas=args.replicas,
            goodput_1=round(goodput_1, 1),
            goodput_n=round(goodput_n, 1),
            batch=args.batch, clients=args.clients,
            scaling_bar_met=bool(bar_met),
            host_cores=ncores,
            dispatches=int(m.counter("fleet.dispatches")),
            platform=platform,
            note=(
                f"{args.replicas} replica PROCESSES vs 1, closed-loop"
                " min_latency through the router; on a"
                f" {ncores}-core host every process shares the core(s) —"
                " the 2x bar needs one core per replica, so"
                " scaling_bar_met carries the honest verdict"
            ),
        )

        # -- phase 2: zero-stale parity per strategy --------------------
        stale = 0
        sample = [POOL[rng.randrange(len(POOL))] for _ in range(200)]
        want = oracle.check(ctx, consistency.full(), *sample)
        zk_head = zookie.mint(router.head_revision, cfg.zookie_key)
        for label, cs, zk in (
            ("full", consistency.full(), None),
            ("at_least+zookie", consistency.min_latency(), zk_head),
            ("min_latency", consistency.min_latency(), None),
        ):
            got = router.check(
                background().with_timeout(60.0), cs, *sample, zookie=zk
            )
            bad = sum(1 for g, w in zip(got, want) if g != w)
            # min_latency without a zookie may serve an older resident
            # revision by CONTRACT — only count it once replicas are
            # provably at head (the zookie row just forced catchup)
            stale += bad
            note(f"parity[{label}]: {bad} mismatches / {len(sample)}")

        toggled = rel.must_from_triple("repo:r0", "reader", "user:toggler")
        probe = rel.must_from_triple("repo:r0", "read", "user:toggler")
        for k in range(args.toggles):
            txn = rel.Txn()
            on = (k % 2 == 0)
            (txn.touch if on else txn.delete)(toggled)
            zk = router.write(ctx, txn)
            got = router.check(
                background().with_timeout(60.0),
                consistency.min_latency(), probe, zookie=zk,
            )
            if got[0] is not on:
                stale += 1
        note(f"dynamic zookie toggling: {args.toggles} write->read edges,"
             f" {stale} total staleness violations")
        emit(
            "fleet_zero_stale", stale, "violations",
            1.0 if stale == 0 else 0.0,
            sample=len(sample), toggles=args.toggles,
            strategies="full,at_least+zookie,min_latency",
            fresh_waits=int(m.counter("fleet.fresh_waits")),
            freshness_redirects=int(m.counter("fleet.freshness_redirects")),
            platform=platform,
            note=(
                "host-oracle parity per strategy + dynamic"
                " toggling-edge zookie read-your-writes; every verdict"
                " compared at the revision its strategy promises"
            ),
        )

        # -- phase 3: seeded mid-run kill + failover p99 ----------------
        def timed_checks(n, victim_at=None, victim=None):
            lat, answers = [], 0
            for k in range(n):
                if victim_at is not None and k == victim_at:
                    victim.send_signal(signal.SIGKILL)
                    note(f"SIGKILL -> replica process at request {k}")
                s = rng.randrange(len(POOL) - 8)
                qs = POOL[s:s + 8]
                t0 = time.perf_counter()
                got = router.check(
                    background().with_timeout(60.0),
                    consistency.full(), *qs,
                )
                lat.append((time.perf_counter() - t0) * 1000.0)
                wq = oracle.check(background(), consistency.full(), *qs)
                if got != wq:
                    raise RuntimeError(f"stale/wrong answer at request {k}")
                answers += 1
            return np.asarray(lat), answers

        base_lat, _ = timed_checks(max(args.failover_checks // 2, 50))
        base_p99 = float(np.percentile(base_lat, 99))
        kills_before = m.counter("fleet.kill_detections")
        victim_proc = procs["r1"][0]
        n_win = args.failover_checks
        win_lat, answers = timed_checks(
            n_win, victim_at=n_win // 4, victim=victim_proc,
        )
        victim_proc.wait(timeout=30.0)
        failover_p99 = float(np.percentile(win_lat, 99))
        lost = n_win - answers
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if ("r1" not in router.status()["ring"]
                    and m.counter("fleet.kill_detections") > kills_before):
                break
            time.sleep(0.05)
        assert m.counter("fleet.kill_detections") > kills_before, (
            "SIGKILL never detected"
        )
        rec.flush()
        incidents = [e for e in rec.incident_index()
                     if e["trigger"] == "fleet.failover"]
        assert incidents, "no fleet.failover incident bundle written"

        # restart: a fresh process bootstraps, catches up, rejoins
        t0 = time.perf_counter()
        p, h, prt = spawn_replica(
            py, router.port, "r1b", env,
            os.path.join(incident_dir, "r1b.stderr"),
        )
        procs["r1b"] = (p, h, prt)
        router.add_replica(h, prt, wait_ready_s=60.0)
        rejoin_s = time.perf_counter() - t0
        post = router.check(
            background().with_timeout(60.0), consistency.full(), *sample
        )
        assert post == want, "restarted fleet diverged from oracle"
        note(
            f"failover: p99 {base_p99:.1f} -> {failover_p99:.1f} ms through"
            f" the kill window; {answers}/{n_win} answered (lost={lost},"
            f" dup=0 by construction — one verdict list per request);"
            f" restart+rejoin {rejoin_s:.1f}s"
        )
        emit(
            "failover_p99_ms", round(failover_p99, 3), "ms",
            round(base_p99 / max(failover_p99, 1e-9), 4),
            baseline_p99_ms=round(base_p99, 3),
            p99_vs_baseline=round(failover_p99 / max(base_p99, 1e-9), 3),
            window_checks=n_win, lost=int(lost), dup=0, stale=0,
            reroutes=int(m.counter("fleet.reroutes")),
            evictions=int(m.counter("fleet.evictions")),
            kill_detections=int(m.counter("fleet.kill_detections")),
            incidents=len(incidents),
            rejoin_s=round(rejoin_s, 2),
            platform=platform,
            note=(
                "full-consistency p99 across a seeded SIGKILL of one"
                " replica process; every request answered exactly once"
                " and verified against the host oracle (zero"
                " lost/dup/stale), kill detected -> ring eviction +"
                " fleet.failover incident, restarted replica re-joined"
            ),
        )
        assert lost == 0 and stale == 0
        return 0
    finally:
        trace.install_recorder(None)
        router.close()
        for p, _, _ in procs.values():
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10.0)


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
