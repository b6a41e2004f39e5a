"""Multi-host (multi-process) deployment: jax.distributed startup, a
global mesh spanning processes, and the 2-process CPU dryrun that proves
the bucket-sharded tables + replicated overlays work across process
boundaries.

This is the distributed-communication backend SURVEY.md §5 maps from the
reference's gRPC process boundary (/root/reference/client/client.go:31-61):
collectives ride ICI *within* a slice and DCN *across* slices, selected
by XLA from the mesh layout — the code is identical either way.

Deployment shape for BASELINE config 5's v5e-16 (two v5e-8 slices):

- one process per host; each calls :func:`initialize` (coordinator =
  host 0), then builds the SAME snapshot tables from its replicated
  store feed — the standard multihost pattern: identical host inputs +
  ``jax.device_put(x, NamedSharding(global_mesh, spec))`` yield one
  consistent global array.
- mesh ``(data, model)`` from :func:`global_mesh`: the model (edge-
  bucket) axis should stay WITHIN a slice so the per-probe psum-OR /
  single-owner broadcasts ride ICI; the data (query-batch) axis crosses
  slices over DCN, where the only traffic is the per-dispatch query
  matrix and the result planes (no per-probe collectives cross DCN).
  ``global_mesh`` lays devices out process-major, which produces exactly
  that split when ``data`` is a multiple of the process count.
- Watch deltas: the ``dl_*`` overlays are replicated (engine/flat.py),
  so each host ships the same small overlay per revision — the
  cross-host delta path costs O(delta) per host, never O(E/M)·M.

The dryrun (driver hook: ``__graft_entry__.dryrun_multichip``'s
multi-process mode) runs this file as a module in N spawned processes on
the CPU backend (the moral equivalent of serve-testing, SURVEY.md §4)
and verifies every process's local result shards against the host
oracle.  Both 2-process (4 devices each) and 4-process (2 devices each)
splits are exercised by tests/test_multihost.py.

Measured per-dispatch collective accounting (StableHLO lowering of the
shard_mapped flat kernel on the virtual 8-device mesh, feature schema
with walked userset/arrow/exclusion sites — r05):

- every collective is an ``all_reduce`` whose replica groups span ONLY
  the model axis (e.g. ``[[0,1],[2,3],[4,5],[6,7]]`` on a 4x2 mesh):
  the per-probe psum-OR / single-owner broadcasts stay within a data
  row, i.e. on ICI when the model axis is laid out within a slice;
- count: 17 reduces/dispatch on the feature schema (one per walked
  probe site); a fully folded schema (config-2 shape) drops to 6;
- payload: int32[B/data] per reduce -> 17 B per query per dispatch
  crossing ICI, independent of batch size (measured identical at
  B=8192 and B=131072);
- NOTHING crosses the data axis inside the kernel: the DCN-analogue
  boundary carries only the packed query matrix in (32 B/query) and
  the three result planes out (3 B/query) per dispatch.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional

def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """``jax.distributed.initialize`` with env-var defaults
    (GOCHUGARU_COORDINATOR / GOCHUGARU_NUM_PROCESSES /
    GOCHUGARU_PROCESS_ID) — call once per process, before any jax
    computation.  On a single process (no env, no args) this is a no-op
    so the same entrypoint serves both deployments."""
    coordinator_address = coordinator_address or os.environ.get(
        "GOCHUGARU_COORDINATOR"
    )
    if num_processes is None:
        num_processes = int(os.environ.get("GOCHUGARU_NUM_PROCESSES") or "1")
    if process_id is None:
        process_id = int(os.environ.get("GOCHUGARU_PROCESS_ID") or "0")
    if num_processes <= 1:
        return
    if not coordinator_address:
        # fail FAST: silently running each host as its own single-process
        # JAX would surface only as a confusing mesh-size error later
        raise ValueError(
            "multi-process init requires a coordinator address "
            "(GOCHUGARU_COORDINATOR) when GOCHUGARU_NUM_PROCESSES > 1"
        )
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_mesh(data: int, model: int):
    """A (data × model) mesh over every device of every process,
    process-major: with ``data`` a multiple of the process count, each
    data row's ``model`` group stays within one process/slice (probe
    collectives on ICI; only the batch axis crosses DCN)."""
    from .mesh import make_mesh

    return make_mesh(data, model)


def owned_model_shards(mesh):
    """Model-shard indices whose mesh column contains at least one of
    THIS process's devices — the ownership set the feed partition
    materializes rows for (engine/partition.py partition_feed).  On a
    mesh whose model axis spans processes (e.g. ``global_mesh(1, n)``)
    the sets are disjoint and per-process host RSS is O(E·|owned|/M);
    on the within-slice layout every process owns all M shards and the
    win is the O(E/M) build scratch alone."""
    import numpy as np

    import jax

    pid = jax.process_index()
    devs = np.asarray(mesh.devices)
    if devs.ndim == 1:
        devs = devs[None, :]
    return tuple(
        m for m in range(devs.shape[1])
        if any(d.process_index == pid for d in devs[:, m].flat)
    )


# ---------------------------------------------------------------------------
# 2-process CPU dryrun
# ---------------------------------------------------------------------------


def _worker_main() -> None:
    """One dryrun process: init distributed CPU JAX, build the shared
    world, run the sharded check step over the GLOBAL mesh, verify the
    locally-addressable result rows against the host oracle."""
    from gochugaru_tpu.utils.platform import force_cpu_platform

    n_local = int(os.environ["GOCHUGARU_DRYRUN_LOCAL_DEVICES"])
    force_cpu_platform(n_local)
    initialize()
    import numpy as np

    import jax

    import __graft_entry__ as ge
    from gochugaru_tpu.engine.oracle import T
    from gochugaru_tpu.parallel import ShardedEngine

    pid = jax.process_index()
    n_dev = len(jax.devices())
    model = 2 if n_local % 2 == 0 else 1
    data = n_dev // model
    mesh = global_mesh(data, model)

    cs, snap, oracle, checks = ge._world(n_checks=32)
    engine = ShardedEngine(cs, mesh)
    dsnap = engine.prepare(snap)
    queries, qctx = engine._lower_queries(snap, checks, dsnap.strings)
    d, p, ovf = engine._dispatch_columns(
        dsnap, queries, qctx, ge.NOW_US, fetch=False
    )

    def verify(d_out, ovf_out) -> int:
        # every process verifies ITS addressable shard rows (deduped: the
        # model axis replicates each data shard); row index = the global
        # position on the data-partitioned axis 0
        seen = set()
        checked = 0
        for shard, oshard in zip(
            d_out.addressable_shards, ovf_out.addressable_shards
        ):
            lo = shard.index[0].start or 0
            if lo in seen:
                continue
            seen.add(lo)
            vals = np.asarray(shard.data)
            ovals = np.asarray(oshard.data)
            for j, got in enumerate(vals):
                gi = lo + j
                if gi >= len(checks):
                    continue
                assert not ovals[j], (
                    f"proc {pid}: unexpected overflow at {checks[gi]} (row {gi})"
                )
                want = oracle.check_relationship(checks[gi]) == T
                assert bool(got) == want, (
                    f"proc {pid}: mismatch at {checks[gi]} (row {gi})"
                )
                checked += 1
        return checked

    checked = verify(d, ovf)

    # partitioned-feed prepare over the SAME world: each process
    # materializes only its owned bucket shards from the feed columns
    # (engine/partition.py), and the dispatch must verify identically
    part_checked = -1
    if os.environ.get("GOCHUGARU_DRYRUN_PARTITION", "1") == "1":
        from gochugaru_tpu.engine.partition import (
            partition_feed,
            snapshot_raw_columns,
        )

        cols = snapshot_raw_columns(snap)
        part = partition_feed(
            snap.revision, cs, snap.interner, cols, engine.config,
            engine.model_size, owned=owned_model_shards(mesh),
            contexts=snap.contexts, epoch_us=ge.NOW_US,
        )
        assert part is not None
        dsnap2 = engine.prepare_partitioned(part)
        d2, _p2, ovf2 = engine._dispatch_columns(
            dsnap2, queries, qctx, ge.NOW_US, fetch=False
        )
        part_checked = verify(d2, ovf2)
        assert part_checked == checked
    print(f"DRYRUN-OK proc={pid} devices={n_dev} mesh={data}x{model} "
          f"verified={checked}/{len(checks)} partitioned={part_checked}",
          flush=True)


def dryrun_multihost(
    n_processes: int = 2, n_devices: int = 8, timeout_s: int = 600
) -> None:
    """Spawn ``n_processes`` CPU processes (each with
    ``n_devices // n_processes`` virtual devices), run the full sharded
    check step over the process-spanning global mesh, and require every
    process to verify its result shards.  The multi-process analogue of
    ``__graft_entry__.dryrun_multichip``."""
    assert n_devices % n_processes == 0
    local = n_devices // n_processes
    # a fresh coordinator port per run: a stale worker from a timed-out
    # previous run holding the hardcoded port would otherwise absorb the
    # new run's joins into a zombie coordinator
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    procs = []
    for pid in range(n_processes):
        env = dict(
            os.environ,
            GOCHUGARU_COORDINATOR=coordinator,
            GOCHUGARU_NUM_PROCESSES=str(n_processes),
            GOCHUGARU_PROCESS_ID=str(pid),
            GOCHUGARU_DRYRUN_LOCAL_DEVICES=str(local),
            # a CPU dryrun by design: N processes cannot share one chip
            JAX_PLATFORMS="cpu",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "gochugaru_tpu.parallel.multihost"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            )),
        ))
    outs = []
    ok = True
    for pid, pr in enumerate(procs):
        try:
            out, _ = pr.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            pr.kill()
            out, _ = pr.communicate()
            ok = False
        outs.append(out)
        if pr.returncode != 0 or "DRYRUN-OK" not in (out or ""):
            ok = False
    if not ok:
        for pid, out in enumerate(outs):
            tail = "\n".join((out or "").splitlines()[-12:])
            print(f"--- proc {pid} tail ---\n{tail}", file=sys.stderr)
        raise RuntimeError("multi-host dryrun failed")
    total = 0
    want = None
    for out in outs:
        for line in (out or "").splitlines():
            if line.startswith("DRYRUN-OK"):
                print(line)
                frac = line.rsplit("verified=", 1)[1].split()[0]
                k, n = frac.split("/")
                total += int(k)
                want = int(n)
    if want is not None and total < want:
        raise RuntimeError(
            f"dryrun shards covered only {total}/{want} checks across"
            " processes — data-axis partitioning is dropping rows"
        )


# ---------------------------------------------------------------------------
# RSS dryrun: the measured host-sharded-build memory claim
# ---------------------------------------------------------------------------

_RSS_EPOCH = 1_700_000_000_000_000


def _raw_rbac_world(edges: int):
    """The GitHub-RBAC world (bench.py build_world's shape) as UNSORTED
    raw feed columns — what a store feed hands partition_feed, generated
    with deterministic arithmetic (no duplicate rows) so every process
    of an RSS dryrun builds the identical feed."""
    import numpy as np

    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner

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
    itn = Interner()
    n_repos = max(edges // 5, 40)
    n_users = max(n_repos // 10, 70)
    n_teams = max(n_users // 10, 8)
    n_orgs = max(n_teams // 10, 2)
    users = np.asarray(
        [itn.node("user", f"u{i}") for i in range(n_users)], np.int32
    )
    teams = np.asarray(
        [itn.node("team", f"t{i}") for i in range(n_teams)], np.int32
    )
    orgs = np.asarray(
        [itn.node("org", f"o{i}") for i in range(n_orgs)], np.int32
    )
    repos = np.asarray(
        [itn.node("repo", f"r{i}") for i in range(n_repos)], np.int32
    )
    slot = cs.slot_of_name
    member, admin = slot["member"], slot["admin"]
    org_rel, maint, reader = slot["org"], slot["maintainer"], slot["reader"]

    res_p, rel_p, subj_p, srel_p = [], [], [], []

    def add(r, rl, s, sr):
        res_p.append(r.astype(np.int32))
        rel_p.append(np.full(r.shape[0], rl, np.int32))
        subj_p.append(s.astype(np.int32))
        srel_p.append(np.full(r.shape[0], sr, np.int32))

    # team edges budgeted to ~edges/5 (repos carry 4/5); capped under
    # n_users/7 so the 7-stride below stays duplicate-free per team
    per_team = max(2, min((edges // 5) // n_teams, n_users // 7))
    t_idx = np.repeat(np.arange(n_teams), per_team)
    k_idx = np.tile(np.arange(per_team), n_teams)
    add(teams[t_idx], member, users[(t_idx * 13 + 7 * k_idx) % n_users], -1)
    o_idx = np.arange(n_orgs)
    add(orgs, admin, users[o_idx % n_users], -1)
    for j in range(2):  # org member usersets: 2 teams each
        add(orgs, member, teams[(o_idx * 3 + j) % n_teams], member)
    for j in range(5):  # org direct members
        add(orgs, member, users[(o_idx * 11 + j) % n_users], -1)
    r_idx = np.arange(n_repos)
    add(repos, org_rel, orgs[r_idx % n_orgs], -1)
    add(repos, maint, teams[r_idx % n_teams], member)
    for j in range(2):
        add(repos, reader, users[(r_idx * 17 + j * 5 + 1) % n_users], -1)

    cols = dict(
        res=np.concatenate(res_p), rel=np.concatenate(rel_p),
        subj=np.concatenate(subj_p), srel=np.concatenate(srel_p),
    )
    return cs, itn, cols, dict(users=users, repos=repos, slot=slot)


def _rss_env_int(name: str, default: int) -> int:
    return int(os.environ.get(name) or str(default))


def _rss_baseline_main() -> None:
    """Single-process reference: full snapshot + the pre-PR
    build-full-then-stack prepare over the same (1 × n_dev) mesh —
    the denominator of the RSS comparison."""
    import json

    from gochugaru_tpu.utils.platform import force_cpu_platform

    n_dev = _rss_env_int("GOCHUGARU_DRYRUN_DEVICES", 8)
    force_cpu_platform(n_dev)
    import jax

    from gochugaru_tpu.engine.plan import EngineConfig
    from gochugaru_tpu.parallel import ShardedEngine
    from gochugaru_tpu.store.snapshot import build_snapshot_from_columns
    from gochugaru_tpu.utils.metrics import peak_rss_mb

    edges = _rss_env_int("GOCHUGARU_DRYRUN_EDGES", 1_000_000)
    cs, itn, cols, _info = _raw_rbac_world(edges)
    E = int(cols["res"].shape[0])
    jax.devices()
    base = peak_rss_mb()
    snap = build_snapshot_from_columns(
        1, cs, itn, epoch_us=_RSS_EPOCH, **cols
    )
    del cols
    engine = ShardedEngine(
        cs, global_mesh(1, n_dev),
        EngineConfig.for_schema(cs, flat_partition_build=False),
    )
    dsnap = engine.prepare(snap)
    assert dsnap.flat_meta is not None and dsnap.flat_meta.sharded
    peak = peak_rss_mb()
    print("RSS-BASELINE " + json.dumps(dict(
        edges=E, base_mb=base, peak_mb=peak,
        build_delta_mb=round(peak - base, 1),
    )), flush=True)


def _rss_worker_main() -> None:
    """One multi-process RSS worker: feed-partitioned prepare over a
    mesh whose MODEL axis spans the processes, so ownership is disjoint
    and each process materializes only its share of the feed."""
    import json

    from gochugaru_tpu.utils.platform import force_cpu_platform

    n_local = _rss_env_int("GOCHUGARU_DRYRUN_LOCAL_DEVICES", 4)
    force_cpu_platform(n_local)
    initialize()
    import numpy as np

    import jax

    from gochugaru_tpu.engine.partition import partition_feed
    from gochugaru_tpu.engine.plan import EngineConfig
    from gochugaru_tpu.parallel import ShardedEngine
    from gochugaru_tpu.utils.metrics import peak_rss_mb

    edges = _rss_env_int("GOCHUGARU_DRYRUN_EDGES", 1_000_000)
    n_dev = len(jax.devices())
    mesh = global_mesh(1, n_dev)
    cs, itn, cols, info = _raw_rbac_world(edges)
    E = int(cols["res"].shape[0])
    base = peak_rss_mb()
    engine = ShardedEngine(cs, mesh, EngineConfig.for_schema(cs))
    owned = owned_model_shards(mesh)
    part = partition_feed(
        1, cs, itn, cols, engine.config, engine.model_size,
        owned=owned, epoch_us=_RSS_EPOCH,
    )
    assert part is not None
    dsnap = engine.prepare_partitioned(part)
    peak = peak_rss_mb()
    print("RSS-OK " + json.dumps(dict(
        proc=int(jax.process_index()), owned=list(owned), edges=E,
        local_rows=int(part.snapshot.e_rel.shape[0]),
        base_mb=base, peak_mb=peak,
        build_delta_mb=round(peak - base, 1),
    )), flush=True)
    # dispatch smoke: some CPU jaxlib builds cannot run multiprocess
    # collectives at all — the BUILD is this mode's claim; correctness
    # of the tables is pinned by the parity child + the partitioned
    # single-process dispatch suites (tests/test_feed_partition.py)
    try:
        rng = np.random.default_rng(3)
        B = 1024
        d, _p, ovf = engine.check_columns(
            dsnap,
            rng.choice(info["repos"], B).astype(np.int32),
            np.full(B, info["slot"]["read"], np.int32),
            rng.choice(info["users"], B).astype(np.int32),
            now_us=_RSS_EPOCH,
        )
        assert not ovf.any()
        print(f"RSS-DISPATCH-OK granted={int(d.sum())}/{B}", flush=True)
    except Exception as e:  # noqa: BLE001 — reported, not fatal
        print(
            f"RSS-DISPATCH-SKIP {type(e).__name__}: {str(e)[:140]}",
            flush=True,
        )


def _rss_parity_main() -> None:
    """Single-process bitwise check at the harness's world shape: the
    feed-partitioned tables == the pre-PR builder's, array for array."""
    import numpy as np

    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(_rss_env_int("GOCHUGARU_DRYRUN_DEVICES", 8))
    from gochugaru_tpu.engine.flat import build_flat_arrays_sharded
    from gochugaru_tpu.engine.partition import ShardSlices, partition_feed
    from gochugaru_tpu.engine.plan import EngineConfig
    from gochugaru_tpu.store.snapshot import build_snapshot_from_columns

    edges = min(_rss_env_int("GOCHUGARU_DRYRUN_EDGES", 1_000_000), 300_000)
    M = _rss_env_int("GOCHUGARU_DRYRUN_DEVICES", 8)
    cs, itn, cols, _info = _raw_rbac_world(edges)
    snap = build_snapshot_from_columns(
        1, cs, itn, epoch_us=_RSS_EPOCH,
        **{k: v.copy() for k, v in cols.items()},
    )
    cfg = EngineConfig.for_schema(cs)
    # the reference MUST be the pre-PR build-full-then-stack path — with
    # the partition-first default both sides would share the new
    # machinery and a shared bug would cancel out of the comparison
    legacy = EngineConfig.for_schema(cs, flat_partition_build=False)
    built = build_flat_arrays_sharded(snap, legacy, M, plan=None)
    assert built is not None
    ref, ref_meta, _f, _c = built
    part = partition_feed(1, cs, itn, cols, cfg, M, epoch_us=_RSS_EPOCH)
    assert part is not None and part.meta == ref_meta
    assert set(part.arrays) == set(ref)
    for k in sorted(ref):
        v = part.arrays[k]
        got = v.to_full() if isinstance(v, ShardSlices) else v
        assert np.array_equal(got, ref[k]), f"table {k} differs"
    print(f"PARITY-OK tables={len(ref)} edges={snap.num_edges}", flush=True)


def _spawn_rss(mode: str, extra_env: dict, timeout_s: int):
    env = dict(
        os.environ,
        GOCHUGARU_DRYRUN_MODE=mode,
        JAX_PLATFORMS="cpu",
        **extra_env,
    )
    return subprocess.Popen(
        [sys.executable, "-m", "gochugaru_tpu.parallel.multihost"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )),
    )


def _communicate(pr, timeout_s: int):
    try:
        out, _ = pr.communicate(timeout=timeout_s)
        return out or "", pr.returncode
    except subprocess.TimeoutExpired:
        pr.kill()
        out, _ = pr.communicate()
        return out or "", -1


def rss_dryrun(
    edges: int = 1_000_000,
    n_processes: int = 2,
    n_devices: int = 8,
    timeout_s: int = 900,
    max_ratio: float = 0.6,
) -> dict:
    """The measured host-sharded-build memory claim, end to end:

    1. single-process baseline — full snapshot + pre-PR
       build-full-then-stack prepare (``flat_partition_build=False``);
    2. bitwise parity child — feed-partitioned tables == the pre-PR
       builder's at the same world (bounded world size: it must hold
       BOTH builds);
    3. ``n_processes`` jax.distributed workers over a (1 × n_devices)
       mesh (model axis spanning processes → disjoint shard ownership),
       each building ONLY its owned partitions via partition_feed.

    Passes when every worker's build-phase RSS delta (peak − post-
    worldgen base: both paths generate the identical feed, so the delta
    isolates feed→tables memory) is ≤ ``max_ratio`` × the baseline's.
    Returns the summary dict; raises on any failure."""
    import json
    import socket

    env_c = dict(
        GOCHUGARU_DRYRUN_EDGES=str(edges),
        GOCHUGARU_DRYRUN_DEVICES=str(n_devices),
    )
    out, rc = _communicate(
        _spawn_rss("rss-baseline", env_c, timeout_s), timeout_s
    )
    base_line = [l for l in out.splitlines() if l.startswith("RSS-BASELINE ")]
    if rc != 0 or not base_line:
        raise RuntimeError(f"rss baseline failed:\n{out[-2000:]}")
    baseline = json.loads(base_line[0].split(" ", 1)[1])
    print(base_line[0], flush=True)

    out, rc = _communicate(
        _spawn_rss("rss-parity", env_c, timeout_s), timeout_s
    )
    if rc != 0 or "PARITY-OK" not in out:
        raise RuntimeError(f"rss parity failed:\n{out[-2000:]}")
    print([l for l in out.splitlines() if "PARITY-OK" in l][0], flush=True)

    assert n_devices % n_processes == 0
    local = n_devices // n_processes
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    procs = [
        _spawn_rss("rss", dict(
            env_c,
            GOCHUGARU_COORDINATOR=coordinator,
            GOCHUGARU_NUM_PROCESSES=str(n_processes),
            GOCHUGARU_PROCESS_ID=str(pid),
            GOCHUGARU_DRYRUN_LOCAL_DEVICES=str(local),
        ), timeout_s)
        for pid in range(n_processes)
    ]
    workers = []
    dispatch_ok = 0
    for pid, pr in enumerate(procs):
        out, rc = _communicate(pr, timeout_s)
        lines = [l for l in out.splitlines() if l.startswith("RSS-OK ")]
        if rc != 0 or not lines:
            tail = "\n".join(out.splitlines()[-12:])
            raise RuntimeError(f"rss worker {pid} failed:\n{tail}")
        workers.append(json.loads(lines[0].split(" ", 1)[1]))
        print(lines[0], flush=True)
        if "RSS-DISPATCH-OK" in out:
            dispatch_ok += 1
        else:
            skip = [l for l in out.splitlines() if "RSS-DISPATCH-SKIP" in l]
            if skip:
                print(f"# worker {pid}: {skip[0]}", flush=True)
    worst = max(w["build_delta_mb"] for w in workers)
    ratio = worst / max(baseline["build_delta_mb"], 1e-9)
    summary = dict(
        edges=baseline["edges"],
        n_processes=n_processes,
        baseline_build_delta_mb=baseline["build_delta_mb"],
        baseline_peak_mb=baseline["peak_mb"],
        worker_build_delta_mb=[w["build_delta_mb"] for w in workers],
        worker_peak_mb=[w["peak_mb"] for w in workers],
        ratio=round(ratio, 3),
        max_ratio=max_ratio,
        dispatch_verified_workers=dispatch_ok,
    )
    print("RSS-SUMMARY " + json.dumps(summary), flush=True)
    if ratio > max_ratio:
        raise RuntimeError(
            f"per-process build RSS {worst} MB is {ratio:.2f}x the "
            f"single-process {baseline['build_delta_mb']} MB "
            f"(bar: {max_ratio})"
        )
    return summary


def _main() -> None:
    if "--rss" in sys.argv[1:]:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--rss", action="store_true")
        ap.add_argument("--edges", type=int, default=1_000_000)
        ap.add_argument("--processes", type=int, default=2)
        ap.add_argument("--devices", type=int, default=8)
        ap.add_argument("--max-ratio", type=float, default=0.6)
        args = ap.parse_args()
        rss_dryrun(
            edges=args.edges, n_processes=args.processes,
            n_devices=args.devices, max_ratio=args.max_ratio,
        )
        return
    mode = os.environ.get("GOCHUGARU_DRYRUN_MODE", "")
    if mode == "rss":
        _rss_worker_main()
    elif mode == "rss-baseline":
        _rss_baseline_main()
    elif mode == "rss-parity":
        _rss_parity_main()
    else:
        _worker_main()


if __name__ == "__main__":
    _main()
