"""Benchmark: BASELINE config 2 — GitHub-style RBAC, 10k repos x 1k users,
2-hop org→team→repo rewrites, 100k-check batches on one chip.

Prints one JSON line per metric (headline first):
  {"metric": ..., "value": N, "unit": "checks/sec/chip", "vs_baseline": N,
   "p99_ms": N, "batch": N, "edges": N, "platform": ...}

``vs_baseline`` is the fraction of the BASELINE.json north-star target
(10M checks/sec/chip); the reference itself publishes no numbers
(BASELINE.md), so the target is the denominator.  ``p99_ms`` is the p99
batch-evaluation latency (north star: p99 < 2 ms, BASELINE.md:22); the
``rbac_2hop_small_batch_p99_latency`` row measures it the way a serving
path would — a warm B=1024 latency-mode dispatch (engine/latency.py)
with its host/H2D/kernel/D2H budget on the row.

Honesty contract: ``value``/``vs_baseline`` are the repeat-harness TRUE
wall-clock rate (N whole-batch evaluations inside one dispatch,
t(2K)-t(K) — nothing overlapped, nothing amortized away); the pipelined
rate (back-to-back queued dispatches) rides along as the secondary
``pipelined_rate`` field.  While the true rate for a batch is still
being measured, a provisional line carries ``rate_basis:
"blocked-dispatch"`` (median individually-blocked dispatch — also
honest wall clock, slightly pessimistic); the final line for the batch
carries ``rate_basis: "repeat-harness"`` and supersedes it.

Process contract:
- the parent NEVER imports jax (a process that has touched JAX holds the
  chip); ONE child does the work under a bounded timeout, on whatever
  device JAX finds there — every row carries its ``platform``;
- the child BATCH-RAMPS (8192 → 32768 → 131072 → 262144) and emits a JSON
  line after EVERY batch size; rows a killed child already printed are
  still relayed, but a child that fails or times out makes this script
  exit non-zero.  There is no probe, no rerun on another backend and no
  placeholder row;
- every stage is stamped on stderr (world/prepare/compile/measure), so a
  timeout names the stage it died in;
- the persistent XLA compile cache is placed by
  ``gochugaru_tpu.utils.platform.configure_compile_cache``.
"""

import json
import os
import subprocess
import sys
import time

CHILD_TIMEOUT_S = int(os.environ.get("GOCHUGARU_BENCH_TPU_TIMEOUT", "300"))
NORTH_STAR = 10_000_000


def stage(msg: str) -> None:
    print(f"# stage[{time.strftime('%H:%M:%S')}]: {msg}", file=sys.stderr, flush=True)


def build_world(n_repos=10_000, n_users=1_000, n_teams=100, n_orgs=10, seed=11):
    import numpy as np

    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner
    from gochugaru_tpu.store.snapshot import build_snapshot_from_columns

    schema = """
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
    """
    cs = compile_schema(parse_schema(schema))
    interner = Interner()
    rng = np.random.default_rng(seed)

    users = np.array([interner.node("user", f"u{i}") for i in range(n_users)], np.int64)
    teams = np.array([interner.node("team", f"t{i}") for i in range(n_teams)], np.int64)
    orgs = np.array([interner.node("org", f"o{i}") for i in range(n_orgs)], np.int64)
    repos = np.array([interner.node("repo", f"r{i}") for i in range(n_repos)], np.int64)

    slot = cs.slot_of_name
    member, admin, org_rel = slot["member"], slot["admin"], slot["org"]
    maintainer, reader = slot["maintainer"], slot["reader"]

    res, rel_s, subj, srel = [], [], [], []

    def add(r, rl, s, sr):
        res.append(r); rel_s.append(rl); subj.append(s); srel.append(sr)

    # team members: each team gets n_users/10 members
    per_team = max(2, n_users // 10)
    for t in teams:
        for u in rng.choice(users, per_team, replace=False):
            add(t, member, u, -1)
    # orgs: admins + team usersets + direct members
    for o in orgs:
        add(o, admin, rng.choice(users), -1)
        for t in rng.choice(teams, 2, replace=False):
            add(o, member, t, member)
        for u in rng.choice(users, 5, replace=False):
            add(o, member, u, -1)
    # repos: org edge + maintainer team + direct readers (vectorized)
    repo_orgs = rng.choice(orgs, n_repos)
    repo_teams = rng.choice(teams, n_repos)
    res.extend(repos); rel_s.extend([org_rel] * n_repos)
    subj.extend(repo_orgs); srel.extend([-1] * n_repos)
    res.extend(repos); rel_s.extend([maintainer] * n_repos)
    subj.extend(repo_teams); srel.extend([member] * n_repos)
    for k in range(2):
        res.extend(repos); rel_s.extend([reader] * n_repos)
        subj.extend(rng.choice(users, n_repos)); srel.extend([-1] * n_repos)

    snap = build_snapshot_from_columns(
        1, cs, interner,
        res=np.asarray(res, np.int64), rel=np.asarray(rel_s, np.int64),
        subj=np.asarray(subj, np.int64), srel=np.asarray(srel, np.int64),
        epoch_us=1_700_000_000_000_000,
    )
    return cs, snap, users, repos, slot


def _flat_args(engine, dsnap, snap, q_res, q_perm, q_subj):
    """Lower pre-interned query columns to the flat kernel + padded args
    (the signature lives in DeviceEngine.flat_fn_and_args)."""
    import jax.numpy as jnp

    queries, qctx = engine._columns_preamble(
        dsnap, q_res, q_perm, q_subj, None, None, None, None
    )
    got = engine.flat_fn_and_args(
        dsnap, queries, qctx,
        jnp.int32(snap.now_rel32(1_700_000_000_000_000)), q_res.shape[0],
    )
    assert got is not None
    return got


def measure_batch(engine, dsnap, snap, users, repos, slot, B):
    """Compile + measure one batch size; returns (result dict,
    (q_perm, args) for the repeat-harness pass).  ``value`` in the
    returned dict is the PROVISIONAL honest rate — the median
    individually-blocked dispatch (no overlap) — which run_bench
    upgrades to the repeat-harness true rate; the pipelined
    (overlapped-dispatch) rate rides as the secondary
    ``pipelined_rate`` field."""
    import numpy as np
    import jax

    rng = np.random.default_rng(5)
    q_res = rng.choice(repos, B).astype(np.int32)
    q_perm = rng.choice(np.array([slot["read"], slot["admin"]], np.int32), B)
    q_subj = rng.choice(users, B).astype(np.int32)
    fn, args = _flat_args(engine, dsnap, snap, q_res, q_perm, q_subj)

    stage(f"compiling B={B}")
    t0 = time.time()
    out = fn(*args)
    jax.block_until_ready(out)
    # one fetch → synchronous stream from here; surface overflow/possible
    # counts so a capped world can't report fantasy throughput silently
    d, p, ovf = jax.device_get(out)
    host_work = int((p[:B] & ~d[:B]).sum() + ovf[:B].sum())
    stage(
        f"first dispatch B={B}: {time.time()-t0:.1f}s"
        f" granted={int(d[:B].sum())} host_fallback={host_work}"
    )

    # pipelined throughput: N back-to-back dispatches, blocked at the end
    stage(f"measuring pipelined rate B={B}")
    reps = 4 if B >= 100_000 else 8
    pipelined_rate = 0.0
    for _ in range(2):
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = time.time() - t0
        pipelined_rate = max(pipelined_rate, reps * B / dt)

    # p99 evaluation latency: blocked per-dispatch timings minus the fixed
    # dispatch round trip of a same-signature null program
    stage(f"measuring p99 B={B}")
    null_fn = jax.jit(
        lambda arrs, tid_map, now, qm, qctx:
        (qm[6] != 0, qm[6] != 0, qm[6] != 0)
    )
    jax.block_until_ready(null_fn(*args))

    def timed(f, reps):
        ts = []
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(f(*args))
            ts.append(time.time() - t0)
        return np.asarray(ts)

    # enough samples that p99 isn't just the max of a handful: scale down
    # only when each blocked dispatch is itself long
    reps = 50 if B <= 40_000 else 20
    overhead = float(np.median(timed(null_fn, 12)))
    raw = timed(fn, reps)
    lat = np.maximum(raw - overhead, 0.0) * 1000.0
    p99_ms = float(np.percentile(lat, 99))
    blocked_rate = B / float(np.median(raw))

    from benchmarks.common import roofline_columns, table_bytes

    out = {
        "metric": "rbac_2hop_bulk_check_throughput",
        "value": round(blocked_rate, 1),
        "unit": "checks/sec/chip",
        "vs_baseline": round(blocked_rate / NORTH_STAR, 4),
        "rate_basis": "blocked-dispatch",
        "pipelined_rate": round(pipelined_rate, 1),
        "p99_ms": round(p99_ms, 3),
        "batch": int(B),
        "edges": int(snap.num_edges),
        "host_fallback": host_work,
        # the HBM roofline columns next to checks/s: resident table
        # bytes per edge + gathered bytes per check (perf ledger) +
        # achieved GB/s against the MEASURED triad-microbench ceiling
        "table_bytes_per_edge": round(
            table_bytes(dsnap) / max(int(snap.num_edges), 1), 2
        ),
        **roofline_columns(blocked_rate, dsnap=dsnap),
        "platform": jax.default_backend(),
    }
    return out, (q_perm, args)


def measure_small_batch(engine, dsnap, snap, users, repos, slot):
    """The latency-mode row: warm B=1024 pinned-kernel dispatch p99 with
    the host/H2D/kernel/D2H stage budget (engine/latency.py) — the half
    of the north-star metric (p99 < 2 ms) a 131k-item scan cannot
    measure.  Measured AND emitted through the shared
    benchmarks.common.emit_small_batch_row, so this row's shape cannot
    drift from the config-1/3/4 rows."""
    import sys

    import numpy as np
    import jax

    from benchmarks.common import emit_small_batch_row

    rng = np.random.default_rng(9)
    B = 1024
    q_res = rng.choice(repos, B).astype(np.int32)
    q_perm = np.full(B, slot["read"], np.int32)
    q_subj = rng.choice(users, B).astype(np.int32)
    stage(f"measuring latency-mode small batch B={B}")
    emit_small_batch_row(
        "rbac_2hop_small_batch_p99_latency", engine, dsnap,
        q_res, q_perm, q_subj, edges=int(snap.num_edges),
        platform=jax.default_backend(),
    )
    sys.stdout.flush()  # the line must survive a mid-ramp child kill


def measure_true_rate(engine, dsnap, B, q_perm, args):
    """Repeat-harness true rate (N evaluations inside ONE dispatch,
    t(2K)-t(K)) — the per-dispatch round trip cancels out.  Runs AFTER the batch's headline line is already on
    stdout, so a hang here can only cost this extra figure."""
    import numpy as np

    from benchmarks.common import measured_rate_flat

    # same slot derivation as DeviceEngine.flat_fn_and_args: the harness
    # must compile the very program being benchmarked
    slots = tuple(sorted({int(s) for s in np.unique(q_perm) if s >= 0}))
    stage(f"measuring repeat-harness true rate B={B}")
    # enough loop iterations that t1 is ~100ms-class: small batches with
    # few iterations let host timing jitter swallow the t2 - t1 signal
    iters = max(16, (1 << 19) // B)
    return round(measured_rate_flat(engine, dsnap, slots, B, args, iters=iters), 1)


def run_bench(batches, world_kw, budget_s):
    import jax

    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    t_start = time.time()
    stage(f"backend={jax.default_backend()}")
    cs, snap, users, repos, slot = build_world(**world_kw)
    stage(f"world built: edges={snap.num_edges}")
    engine = DeviceEngine(cs)
    dsnap = engine.prepare(snap)
    stage("prepared: closure + hash indexes on device")
    assert dsnap.flat_meta is not None

    for i, B in enumerate(batches):
        elapsed = time.time() - t_start
        if i > 0 and elapsed > budget_s * 0.55:
            stage(f"budget {elapsed:.0f}s/{budget_s}s spent; skipping B≥{B}")
            break
        result, tr_inputs = measure_batch(
            engine, dsnap, snap, users, repos, slot, B
        )
        # provisional line FIRST (blocked-dispatch basis): a hang in the
        # repeat harness below still leaves this batch's row on stdout
        print(json.dumps(result), flush=True)
        if time.time() - t_start <= budget_s * 0.7:
            result["value"] = measure_true_rate(engine, dsnap, B, *tr_inputs)
            result["vs_baseline"] = round(result["value"] / NORTH_STAR, 4)
            result["rate_basis"] = "repeat-harness"
            # the roofline columns follow the honest rate upgrade:
            # achieved GB/s is a function of the TRUE rate
            from benchmarks.common import roofline_columns

            result.update(roofline_columns(
                result["value"],
                bytes_per_check=result.get("bytes_per_check"),
            ))
            print(json.dumps(result), flush=True)
        else:
            stage(f"budget: keeping blocked-dispatch value for B={B}")
        if i == 0:
            # the latency-mode p99 row rides right after the first
            # (cheapest) batch: early enough to survive a short child
            # timeout, late enough that the headline is already out
            measure_small_batch(engine, dsnap, snap, users, repos, slot)


def child_main() -> None:
    try:
        # ramp past 131k: with the aligned-table kernel the dispatch is
        # ~6 row gathers, so bigger batches keep amortizing the fixed
        # per-dispatch cost (budget gating skips the tail when short)
        run_bench(
            batches=(8_192, 32_768, 131_072, 262_144),
            world_kw={},
            budget_s=CHILD_TIMEOUT_S,
        )
    finally:
        # --metrics rides up through the parent's metric-line relay
        from benchmarks.common import maybe_emit_metrics_snapshot

        maybe_emit_metrics_snapshot()


HEADLINE_METRIC = "rbac_2hop_bulk_check_throughput"


def _parse_best(stdout: str):
    """Reduce a child's stdout to one line per metric.  For the headline
    throughput metric, repeat-harness lines beat provisional
    blocked-dispatch ones (same batch emits both; the honest final value
    must win regardless of magnitude) and the best batch size wins among
    equals; secondary metrics keep their last emitted line.  Returns
    {metric: line} or None when nothing parsed."""
    by_metric = {}
    for line in (stdout or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "metric" not in parsed or "value" not in parsed:
            continue
        m = parsed["metric"]
        if m != HEADLINE_METRIC:
            by_metric[m] = parsed
            continue
        cur = by_metric.get(m)
        def rank(ln):
            return (ln.get("rate_basis") == "repeat-harness", ln["value"])
        if cur is None or rank(parsed) > rank(cur):
            by_metric[m] = parsed
    return by_metric or None


def _run_child(timeout_s: int):
    """Run the one child; returns ({metric: line}|None, failure_reason).
    A failure reason alongside parsed lines means the child died after
    printing them."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout_s)
        stdout, stderr, rc = r.stdout, r.stderr, r.returncode
        reason = None if rc == 0 else f"child rc={rc}"
    except subprocess.TimeoutExpired as e:
        # relay the per-batch lines already emitted before the kill
        stdout = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        stderr = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        reason = f"child timed out after {timeout_s}s"
    if stderr:
        sys.stderr.write(stderr)
    lines = _parse_best(stdout)
    if lines is None and reason is None:
        reason = "child produced no JSON line"
    return lines, reason


def main() -> int:
    # Parent orchestrator: no jax import here — the child owns the device.
    lines, reason = _run_child(CHILD_TIMEOUT_S)
    # headline first (drivers that read only line 1 keep working), then
    # the secondary metrics (small-batch p99 etc.)
    for m in sorted(lines or {}, key=lambda m: m != HEADLINE_METRIC):
        print(json.dumps(lines[m]))
    if reason is not None:
        sys.stderr.write(f"# bench failed: {reason}\n")
        return 1
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        child_main()
    else:
        sys.exit(main())
