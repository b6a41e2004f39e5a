"""Config-2 mesh comparison + degraded-mode columns (ADVICE item 9
foregrounded): the same GitHub-RBAC world checked on a single device,
a 1×8 mesh, and a 4×2 mesh of the 8-virtual-device CPU proxy — plus a
store-backed degraded-mode phase run under injected faults and a tight
admission gate, so shed-rate and retry-count ride the row and
degraded-mode throughput is visible in the trajectory (Graphulo measures
its degraded mode explicitly; so do we).

One JSON line:
  {"metric": "rbac_2hop_mesh_degraded_comparison", "value": <single
   rate>, ..., "mesh_1x8_rate": N, "mesh_4x2_rate": N,
   "shed_rate": N, "retry_count": N, "faults_injected": N, ...}

CPU-proxy by design (`force_cpu_platform(8)`): sharded throughput has
never been timed even on the virtual mesh (round-5 review, weak #6) — this
row is that timing, plus the collective-overhead ratio a real multichip
run will be judged against.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repos", type=int, default=2000)
    ap.add_argument("--users", type=int, default=500)
    ap.add_argument("--batch", type=int, default=32_768)
    args = ap.parse_args()

    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(8)

    import jax
    import numpy as np

    from benchmarks.common import NORTH_STAR_RATE, emit, note, peak_rss_mb
    from bench import build_world
    from gochugaru_tpu.engine.device import DeviceEngine

    from gochugaru_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    cs, snap, users, repos, slot = build_world(
        n_repos=args.repos, n_users=args.users
    )
    note(f"world: edges={snap.num_edges} repos={args.repos}")
    B = args.batch
    rng = np.random.default_rng(5)
    q_res = rng.choice(repos, B).astype(np.int32)
    q_perm = rng.choice(np.array([slot["read"], slot["admin"]], np.int32), B)
    q_subj = rng.choice(users, B).astype(np.int32)

    def rate_of(engine, label, prepare=None):
        """Steady-state checks/s of one engine's columnar dispatch;
        returns (rate, DeviceSnapshot, warm (d, p, o))."""
        dsnap = (prepare or engine.prepare)(snap)
        fn = lambda: engine.check_columns(
            dsnap, q_res, q_perm, q_subj, now_us=1_700_000_000_000_000
        )
        out0 = fn()  # warm: compile + page-in
        fn()
        reps = 6
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        dt = time.perf_counter() - t0
        note(f"{label}: {reps * B / dt:,.0f} checks/s"
             f" granted={int(out0[0].sum())}")
        return reps * B / dt, dsnap, out0

    single_rate, _ds, single_out = rate_of(DeviceEngine(cs), "single-device")

    mesh_rates = {}
    for shape in ((1, 8), (4, 2)):
        key = f"mesh_{shape[0]}x{shape[1]}_rate"
        try:
            from gochugaru_tpu.parallel import ShardedEngine, make_mesh

            eng = ShardedEngine(cs, make_mesh(*shape))
            mesh_rates[key] = round(rate_of(eng, key)[0], 1)
        except Exception as e:  # mesh unavailable: report, don't die
            note(f"{key} failed: {type(e).__name__}: {e}")
            mesh_rates[key] = None

    # ---- partitioned serving: owner-routed vs replicated, 4 devices -----
    # The pre-PR way to serve a fold-bearing schema collective-free is
    # data-parallel replication (mesh M×1: every device holds the FULL
    # stacked+fold tables, batch splits along data).  The partitioned
    # serve (mesh 1×M, serve="routed") model-splits the primary/fold
    # point tables — O(E/M) HBM per device — and owner-routes each query
    # to its bucket's shard, also with no collective in the compiled
    # program.  Same 4 devices, same batch, same answers; the row is the
    # HBM-per-device vs throughput trade.
    def table_bytes_per_device(dsnap):
        """Max over devices of resident stacked+fold table bytes
        (node_type/caveat-context lookups excluded on both sides)."""
        per = {}
        for k, arr in dsnap.arrays.items():
            if k == "node_type" or k.startswith("ectx_"):
                continue
            for s in arr.addressable_shards:
                per[s.device.id] = (
                    per.get(s.device.id, 0) + int(np.asarray(s.data).nbytes)
                )
        return max(per.values())

    part_fields = {}
    try:
        from gochugaru_tpu.parallel import ShardedEngine, make_mesh

        M = 4
        rep_eng = ShardedEngine(cs, make_mesh(M, 1))
        rep_rate, rep_ds, rep_out = rate_of(
            rep_eng, "replicated 4-dev (data-parallel)"
        )
        rt_eng = ShardedEngine(cs, make_mesh(1, M))
        rt_rate, rt_ds, rt_out = rate_of(
            rt_eng, "partitioned 4-dev (owner-routed)",
            prepare=rt_eng.prepare_snapshot_partitioned,
        )
        if not (rt_ds.flat_meta is not None and rt_ds.flat_meta.part_serve):
            raise RuntimeError("partitioned feed declined the bench world")
        oracle_match = all(
            np.array_equal(a, b) for a, b in zip(single_out, rt_out)
        ) and all(np.array_equal(a, b) for a, b in zip(single_out, rep_out))
        rep_bytes = table_bytes_per_device(rep_ds)
        rt_bytes = table_bytes_per_device(rt_ds)
        note(
            f"table bytes/device: replicated {rep_bytes:,} vs routed"
            f" {rt_bytes:,} ({rt_bytes / rep_bytes:.1%});"
            f" rate routed/replicated {rt_rate / rep_rate:.2f}x"
            f" oracle_match={oracle_match}"
        )
        part_fields = dict(
            routed_rate=round(rt_rate, 1),
            replicated_rate=round(rep_rate, 1),
            table_bytes_per_device=int(rt_bytes),
            replicated_table_bytes_per_device=int(rep_bytes),
            table_bytes_ratio=round(rt_bytes / rep_bytes, 4),
            rate_vs_replicated=round(rt_rate / rep_rate, 4),
            oracle_match=bool(oracle_match),
        )
    except Exception as e:  # mesh/feed unavailable: report, don't die
        note(f"partitioned_serving failed: {type(e).__name__}: {e}")

    # ---- degraded-mode phase: client checks under injected faults ------
    # store-backed world so the full client path (admission gate, retry
    # envelope, breaker) is the thing being measured
    from gochugaru_tpu import consistency, rel
    from gochugaru_tpu.client import (
        new_tpu_evaluator,
        with_admission_control,
        with_latency_mode,
    )
    from gochugaru_tpu.utils import faults
    from gochugaru_tpu.utils import metrics as _metrics
    from gochugaru_tpu.utils.admission import AdmissionConfig
    from gochugaru_tpu.utils.context import background

    c = new_tpu_evaluator(
        with_latency_mode(),
        with_admission_control(
            AdmissionConfig(max_inflight=2, breaker_threshold=4)
        ),
    )
    ctx = background()
    c.write_schema(ctx, """
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
    """)
    wrng = np.random.default_rng(11)
    txn = rel.Txn()
    for i in range(200):
        txn.touch(rel.must_from_triple(
            f"repo:r{i}", "reader", f"user:u{wrng.integers(100)}"
        ))
        txn.touch(rel.must_from_triple(f"repo:r{i}", "org", "org:o0"))
    txn.touch(rel.must_from_triple("org:o0", "admin", "user:u0"))
    c.write(ctx, txn)

    m = _metrics.default
    base = m.snapshot()
    # seeded 5%-probability dispatch faults: the degraded mode under test
    faults.arm("device.dispatch", probability=0.05, seed=42)
    faults.arm("latency.dispatch", probability=0.05, seed=43)

    import threading

    DB, PER_WORKER, WORKERS = 64, 25, 4
    checks_done = [0] * WORKERS

    def worker(w):
        lrng = np.random.default_rng(100 + w)
        for _ in range(PER_WORKER):
            qs = [
                rel.must_from_triple(
                    f"repo:r{lrng.integers(200)}", "read",
                    f"user:u{lrng.integers(100)}",
                )
                for _ in range(DB)
            ]
            c.check(background().with_timeout(30.0), consistency.full(), *qs)
            checks_done[w] += DB

    c.check(ctx, consistency.full(),
            rel.must_from_triple("repo:r0", "read", "user:u0"))  # warm
    # queue-depth sampling during the degraded phase: the gate's
    # in-flight gauge is this path's queue, reported with the SAME
    # column names the serving bench uses (bench9_serve.py), so the
    # sharded and serving stories share a schema
    depth_samples = []
    stop_sampler = threading.Event()

    def depth_sampler():
        while not stop_sampler.is_set():
            depth_samples.append(m.gauge("admission.inflight"))
            time.sleep(0.002)

    sampler_t = threading.Thread(target=depth_sampler, daemon=True)
    sampler_t.start()
    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker, args=(w,)) for w in range(WORKERS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    degraded_dt = time.perf_counter() - t0
    stop_sampler.set()
    sampler_t.join(timeout=1.0)
    faults.reset()
    snap_m = m.snapshot()
    qd = np.asarray(depth_samples) if depth_samples else np.zeros(1)

    def delta(key):
        return snap_m.get(key, 0) - base.get(key, 0)

    total_checks = sum(checks_done)
    sheds = delta("admission.sheds") + delta("admission.deadline_sheds")
    retries = delta("retry.retries")
    injected = delta("faults.injected")
    degraded_rate = total_checks / degraded_dt

    emit(
        "rbac_2hop_mesh_degraded_comparison",
        round(single_rate, 1),
        "checks/sec",
        single_rate / NORTH_STAR_RATE,
        **mesh_rates,
        degraded_rate=round(degraded_rate, 1),
        shed_rate=round(sheds / max(total_checks / DB, 1), 4),
        queue_depth_p50=round(float(np.percentile(qd, 50)), 1),
        queue_depth_max=int(qd.max()),
        retry_count=int(retries),
        faults_injected=int(injected),
        breaker_trips=int(delta("breaker.trips")),
        edges=int(snap.num_edges),
        batch=int(B),
        peak_rss_mb=peak_rss_mb(),
        platform=jax.default_backend(),
        note=(
            "CPU proxy (8 virtual devices); mesh = data x model;"
            " degraded phase: 5% injected dispatch faults,"
            " max_inflight=2, 4 workers"
        ),
    )
    if part_fields:
        emit(
            "partitioned_serving",
            part_fields["routed_rate"],
            "checks/sec",
            part_fields["routed_rate"] / NORTH_STAR_RATE,
            **part_fields,
            edges=int(snap.num_edges),
            batch=int(B),
            platform=jax.default_backend(),
            note=(
                "4-dev CPU proxy: owner-routed partitioned serve"
                f" ({part_fields['table_bytes_ratio']:.0%} table bytes"
                "/device) vs data-parallel replicated baseline"
                f" ({part_fields['rate_vs_replicated']:.2f}x rate),"
                " fold engaged, collective-free both"
            ),
        )
    return 0


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
