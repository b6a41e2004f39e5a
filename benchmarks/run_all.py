"""Run every BASELINE config (BASELINE.md:25-32) and write BENCHMARKS.md.

Each config runs as a bounded child process, one after another, on
whatever device JAX finds there; this parent never imports JAX (a
process that has touched JAX holds the chip), probes nothing and falls
back to nothing.  Every row carries the ``platform`` its child reported,
and the suite exits non-zero when any child fails.

Usage:  python benchmarks/run_all.py [--out BENCHMARKS.md] [--quick]
                                     [--metrics] [--compare]
                                     [--compare-tolerance 0.10]

``--quick`` shrinks configs 3/4/5 (CI-sized smoke run); the committed
BENCHMARKS.md should come from a full run.  ``--metrics`` asks every
bench child to append its final ``metrics.snapshot()`` blob
(GOCHUGARU_BENCH_METRICS=1 → common.maybe_emit_metrics_snapshot), which
lands in a "Metrics snapshots" appendix — a regression row then ships
WITH the counters that explain it.  ``--compare`` runs
scripts/bench_compare.py after the suite — newest committed BENCH_r*
round vs. the previous one, direction-aware, one line per metric — and
the suite exits nonzero when the trajectory regressed beyond the
tolerance.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_config(name, cmd, timeout_s, env):
    """Run one config; returns (json_lines, notes, failure_reason)."""
    t0 = time.time()
    try:
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            cwd=ROOT, env=env,
        )
        stdout, stderr = r.stdout, r.stderr
        reason = None if r.returncode == 0 else f"rc={r.returncode}"
    except subprocess.TimeoutExpired as e:
        stdout = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        stderr = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        reason = f"timed out after {timeout_s}s"
    lines = []
    for line in (stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "metric" in parsed:
                lines.append(parsed)
    notes = [
        ln[1:].strip() for ln in (stderr or "").splitlines() if ln.startswith("#")
    ]
    if reason and not lines:
        tail = (stderr or "").strip().splitlines()
        reason += f": {tail[-1][:160]}" if tail else ""
    print(f"[{name}] {time.time()-t0:.0f}s {len(lines)} metrics"
          + (f" ({reason})" if reason else ""), file=sys.stderr, flush=True)
    return lines, notes, reason


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCHMARKS.md"))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--metrics", action="store_true",
                    help="children append a final metrics.snapshot() blob")
    ap.add_argument("--compare", action="store_true",
                    help="run scripts/bench_compare.py after the suite and"
                         " fail on a BENCH_r* trajectory regression")
    ap.add_argument("--compare-tolerance", type=float, default=0.10,
                    help="relative worsening tolerated by --compare")
    args = ap.parse_args()

    env = dict(os.environ)
    if args.metrics:
        env["GOCHUGARU_BENCH_METRICS"] = "1"
    py = sys.executable

    q = args.quick
    configs = [
        ("1 — founders CheckAll (client round trip)",
         [py, "benchmarks/bench1_founders.py"], 420),
        ("2 — GitHub RBAC 2-hop, 100k batch (driver headline)",
         [py, "bench.py"], 700),
        ("3 — Google-Docs nested groups, 1M docs / 10M edges, 5-hop",
         [py, "benchmarks/bench3_docs.py"], 2400),
        ("4 — multi-tenant caveats" + (" (quick)" if q else ", 100M edges"),
         [py, "benchmarks/bench4_caveats.py"]
         + (["--edges", "2000000"] if q else ["--edges", "100000000"]),
         2400),
        ("5 — Watch-driven incremental re-index" + (" (quick)" if q else ""),
         [py, "benchmarks/bench5_watch.py"]
         + (["--edges", "1000000"] if q else ["--edges", "10000000"]),
         1500),
        ("6 — bulk import/export through the Client" + (" (quick)" if q else ""),
         [py, "benchmarks/bench_import.py"]
         + (["--edges", "1000000"] if q else ["--edges", "10000000"]),
         2400),
    ]
    # appended (not inserted) so the --quick index overrides above keep
    # pointing at the rows they name
    configs.append((
        "2m — config-2 CPU mesh comparison + degraded-mode columns",
        [py, "benchmarks/bench2_mesh.py"]
        + (["--repos", "500", "--batch", "8192"] if q else []),
        900,
    ))
    configs.append((
        "7 — incremental closure: member-edge write throughput"
        + (" (quick)" if q else ""),
        [py, "benchmarks/bench6_closure.py"]
        + (["--edges", "1000000", "--rounds", "10", "--warmup", "5"]
           if q else ["--edges", "10000000"]),
        4000,
    ))
    configs.append((
        "8 — partitioned-serving smoke (2-shard parity + routed serve)",
        ["bash", "scripts/partition_smoke.sh"],
        600,
    ))
    configs.append((
        "9 — HBM-lean packed tables: bytes reduction + parity @ config 3"
        + (" (quick, 5% scale)" if q else ""),
        [py, "benchmarks/bench7_hbm.py"]
        + (["--scale", "0.05"] if q else []),
        3600,
    ))
    configs.append((
        "10 — HBM-lean smoke (packed-vs-unpacked parity + bytes bar)",
        ["bash", "scripts/hbm_smoke.sh"],
        600,
    ))
    configs.append((
        "11 — bulk lookup: frontier SpMV candidates/s @ config 3"
        + (" (quick, 5% scale)" if q else ""),
        [py, "benchmarks/bench8_lookup.py"]
        + (["--scale", "0.05"] if q else []),
        2400,
    ))
    configs.append((
        "12 — lookup smoke (walker parity + paginated answer + routed shards)",
        ["bash", "scripts/lookup_smoke.sh"],
        600,
    ))
    configs.append((
        "13 — continuous batching: open-loop goodput/p99 @ offered load"
        + (" (quick)" if q else ""),
        [py, "benchmarks/bench9_serve.py"] + (["--quick"] if q else []),
        900,
    ))
    configs.append((
        "14 — serve smoke (concurrent submitters, oracle parity, shed path)",
        ["bash", "scripts/serve_smoke.sh"],
        600,
    ))
    configs.append((
        "15 — SLO/incident smoke (breaker trip -> incident bundle + burn)",
        ["bash", "scripts/slo_smoke.sh"],
        600,
    ))
    configs.append((
        "16 — perf-attribution smoke (roofline microbench + /perf ledger"
        " + wall-time closure)",
        ["bash", "scripts/perf_smoke.sh"],
        600,
    ))
    configs.append((
        "17 — verdict-cache smoke (oracle parity incl. cached answers,"
        " cache-off bitwise parity, hit-rate floor, chaos on"
        " cache.lookup)",
        ["bash", "scripts/cache_smoke.sh"],
        600,
    ))
    configs.append((
        "18 — decision-provenance smoke (explain==oracle parity, witness"
        " subset, denial frontier, cache re-derivation, decision-log"
        " rotation + denial-rate SLO)",
        ["bash", "scripts/explain_smoke.sh"],
        600,
    ))
    configs.append((
        "19 — unified-SpMM smoke (fused-vs-legacy parity through"
        " check/lookup/fold, one-dispatch multi-hop fixpoint, routed"
        " shards)",
        ["bash", "scripts/spmm_smoke.sh"],
        600,
    ))
    configs.append((
        "20 — fleet serving: replica processes, goodput scaling,"
        " zero-stale per strategy, seeded kill + failover p99"
        + (" (quick)" if q else ""),
        [py, "benchmarks/bench10_fleet.py"] + (["--quick"] if q else []),
        900,
    ))
    configs.append((
        "21 — fleet smoke (self-joining replica processes, zookie"
        " read-your-writes, SIGKILL survival with zero lost/dup/stale)",
        ["bash", "scripts/fleet_smoke.sh"],
        600,
    ))
    configs.append((
        "22 — self-tuning A/B: tuned config vs presets on a mixed"
        " workload, predicted-vs-measured deltas, non-pow2 tier parity"
        + (" (quick)" if q else ""),
        [py, "benchmarks/bench11_tune.py"] + (["--quick"] if q else []),
        1800,
    ))
    configs.append((
        "23 — tune smoke (offline diff fixed point, online controller"
        " bounded moves + revert)",
        ["bash", "scripts/tune_smoke.sh"],
        600,
    ))
    configs.append((
        "24 — group-commit write pipeline: coalesced vs one-at-a-time"
        " writes, bitwise oracle parity, chain compaction, mixed soak"
        + (" (quick)" if q else ""),
        [py, "benchmarks/bench12_writes.py"] + (["--quick"] if q else []),
        900,
    ))
    if not q:
        # Leopard-scale CPU proxy (VERDICT r04 item 3): the same Watch
        # re-index loop at a 100M-edge base — BASELINE config 5's
        # per-chip slice of the 1B / v5e-16 deployment
        configs.insert(5, (
            "5b — Watch re-index, 100M-edge base (Leopard-scale proxy)",
            [py, "benchmarks/bench5_watch.py", "--edges", "100000000"],
            7200,
        ))
    if q:
        configs[2] = (
            "3 — Google-Docs nested groups (quick, 5% scale)",
            [py, "benchmarks/bench3_docs.py", "--scale", "0.05"], 900,
        )

    rows = []
    all_notes = []
    snapshots = []  # (config name, metrics.snapshot() dict) from --metrics
    failed = []  # configs whose child exited non-zero or timed out
    platforms = set()
    for name, cmd, timeout_s in configs:
        lines, notes, reason = run_config(name, cmd, timeout_s, env)
        all_notes.append((name, notes))
        if reason:
            failed.append(f"{name}: {reason}")
        platforms.update(
            p["platform"] for p in lines if p.get("platform")
        )
        if not lines:
            rows.append((name, "—", "failed", "—", "—", "—", "—", "—",
                         reason or "no output"))
            continue
        for parsed in lines:
            if parsed.get("metric") == "metrics_snapshot":
                # child's final counter dump: appendix, not a table row
                snapshots.append((name, parsed.get("snapshot") or {}))
                continue
            vs = parsed.get("vs_baseline")
            rows.append((
                name,
                parsed.get("metric", "?"),
                f"{parsed.get('value', 0):,.1f}",
                parsed.get("unit", ""),
                f"{vs:.4f}" if isinstance(vs, (int, float)) else "—",
                f"{parsed['edges']:,}" if "edges" in parsed else "—",
                f"{parsed['batch']:,}" if "batch" in parsed else "—",
                parsed.get("platform", "—"),
                parsed.get("note", ""),
            ))

    stamp = time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
    with open(args.out, "w") as f:
        f.write("# BENCHMARKS\n\n")
        f.write(
            f"All five BASELINE configs (BASELINE.md:25-32), run {stamp} on"
            f" platform **{', '.join(sorted(platforms)) or 'none'}** via"
            " `python benchmarks/run_all.py"
            + (" --quick" if q else "") + "`.\n\n"
            "North star: ≥10M checks/sec/chip, p99 < 2 ms @ 100M edges"
            " (BASELINE.md:20-23).  The reference publishes no numbers"
            " (BASELINE.md:3-8); the target is the denominator for"
            " vs_baseline in each bench's JSON output.\n\n"
        )
        f.write(
            "| Config | Metric | Value | Unit | vs north star | Edges | Batch | Platform | Note |\n"
            "|---|---|---|---|---|---|---|---|---|\n"
        )
        for r in rows:
            f.write("| " + " | ".join(str(x) for x in r) + " |\n")
        f.write("\n## Runner notes (stderr `#` lines)\n\n")
        for name, notes in all_notes:
            f.write(f"### {name}\n\n")
            for n in notes:
                f.write(f"- {n}\n")
            f.write("\n")
        if snapshots:
            f.write("## Metrics snapshots (--metrics)\n\n")
            f.write(
                "Each bench child's final `metrics.snapshot()` — the"
                " counters/gauges/timer percentiles behind the rows"
                " above.\n\n"
            )
            for name, snap in snapshots:
                f.write(f"### {name}\n\n```json\n")
                f.write(json.dumps(snap, indent=1, sort_keys=True))
                f.write("\n```\n\n")
    print(f"wrote {args.out}", file=sys.stderr)
    for f_ in failed:
        print(f"FAILED {f_}", file=sys.stderr)
    if args.compare:
        # trajectory gate: the suite's verdict includes "did the
        # committed round-over-round numbers regress"
        r = subprocess.run(
            [py, "scripts/bench_compare.py",
             "--tolerance", str(args.compare_tolerance)],
            cwd=ROOT,
        )
        if r.returncode != 0:
            print("bench trajectory REGRESSED (see table above)",
                  file=sys.stderr)
            return r.returncode
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
