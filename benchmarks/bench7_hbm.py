"""Bench 7 — HBM-lean packed tables at the config-3 world.

Packed (bit-packed uint16 lanes + dictionary until-columns + delta-run
ranges + offset residuals + bounded bucket growth; engine/packed.py)
vs the unpacked parity oracle (``flat_packed=False``), measured on the
Google-Docs nested-groups world of BASELINE config 3:

- ``hbm_table_bytes_reduction`` — resident device-table bytes,
  unpacked / packed (bar: ≥ 2.5×), with ``table_bytes_per_edge`` and
  the estimated gathered ``bytes_per_check`` for BOTH layouts on the
  row (the roofline columns next to checks/s);
- ``hbm_packed_true_rate`` — repeat-harness TRUE checks/s of the packed
  layout, ``vs_unpacked`` on the row (bar: within 10%);
- ``oracle_match`` — packed vs unpacked dispatch results bit-for-bit
  over the whole batch (the parity contract), plus a sampled host-
  oracle cross-check;
- ``hbm_packed_small_batch_p99_latency`` — the PINNED latency tier
  serving the packed layout (budget breakdown on the row; parity with
  the throughput path asserted first);
- ``hbm_routed_partitioned_bytes_per_device`` — the owner-routed
  partitioned serve (M=4 CPU proxy) on the packed layout: per-device
  resident bytes vs the packed single-chip footprint, routed dispatch
  parity asserted.

Usage: python benchmarks/bench7_hbm.py [--scale 1.0] [--mesh 4]
"""

import argparse
import os as _os
import sys as _sys
import time

import numpy as np

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

from benchmarks.common import (
    NORTH_STAR_P99_MS,
    NORTH_STAR_RATE,
    emit,
    emit_small_batch_row,
    est_bytes_per_check,
    start_backend,
    measured_rate_flat,
    note,
    roofline_columns,
    table_bytes,
)

_args = argparse.ArgumentParser()
_args.add_argument("--scale", type=float, default=1.0)
_args.add_argument("--mesh", type=int, default=4)
_ARGS = _args.parse_known_args()[0]

EPOCH = 1_700_000_000_000_000
BYTES_BAR = 2.5  # acceptance: ≥2.5x table-bytes reduction
RATE_BAR = 0.90  # acceptance: packed true rate within 10% of unpacked


def _prepare(cs, snap, packed: bool):
    from gochugaru_tpu.engine.device import DeviceEngine
    from gochugaru_tpu.engine.plan import EngineConfig

    eng = DeviceEngine(cs, EngineConfig.for_schema(cs, flat_packed=packed))
    t0 = time.perf_counter()
    dsnap = eng.prepare(snap)
    note(
        f"{'packed' if packed else 'unpacked'} prepare:"
        f" {time.perf_counter() - t0:.1f}s,"
        f" {table_bytes(dsnap) / 1e6:.1f} MB device tables"
    )
    assert dsnap.flat_meta is not None
    assert bool(dsnap.flat_meta.packed) == packed
    return eng, dsnap


def _dispatch_once(eng, dsnap, snap, q_res, q_perm, q_subj):
    import jax
    import jax.numpy as jnp

    queries, qctx = eng._columns_preamble(
        dsnap, q_res, q_perm, q_subj, None, None, None, None
    )
    fn, args = eng.flat_fn_and_args(
        dsnap, queries, qctx, jnp.int32(snap.now_rel32(EPOCH)),
        q_res.shape[0],
    )
    out = fn(*args)
    jax.block_until_ready(out)
    d, p, ovf = jax.device_get(out)
    B = q_res.shape[0]
    return (d[:B], p[:B], ovf[:B]), args


def main() -> None:
    plats = _os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if plats.startswith("cpu"):
        # the routed section needs a multi-device proxy: 8 virtual CPU
        # devices, set BEFORE the backend initializes (bench2's recipe)
        from gochugaru_tpu.utils.platform import force_cpu_platform

        force_cpu_platform(8)
    note(f"platform={start_backend()}")
    _sys.argv = [_sys.argv[0], "--scale", str(_ARGS.scale)]
    from benchmarks.bench3_docs import build_world

    cs, snap, users, docs, slot = build_world()
    note(f"edges={snap.num_edges} nodes={snap.num_nodes}")
    E = int(snap.num_edges)

    rng = np.random.default_rng(7)
    B = 1 << 17
    q_res = rng.choice(docs, B).astype(np.int32)
    q_perm = np.full(B, slot["view"], np.int32)
    q_subj = rng.choice(users, B).astype(np.int32)
    slots = (int(slot["view"]),)

    # ---- unpacked oracle layout ---------------------------------------
    eng_u, ds_u = _prepare(cs, snap, packed=False)
    bytes_u = table_bytes(ds_u)
    bpc_u = est_bytes_per_check(ds_u)
    res_u, args_u = _dispatch_once(eng_u, ds_u, snap, q_res, q_perm, q_subj)
    try:
        rate_u = measured_rate_flat(eng_u, ds_u, slots, B, args_u)
        basis = "repeat-harness"
    except RuntimeError as e:
        note(f"unpacked repeat harness: {e}")
        rate_u, basis = 0.0, "unavailable"

    # ---- packed layout -------------------------------------------------
    eng_p, ds_p = _prepare(cs, snap, packed=True)
    bytes_p = table_bytes(ds_p)
    bpc_p = est_bytes_per_check(ds_p)
    res_p, args_p = _dispatch_once(eng_p, ds_p, snap, q_res, q_perm, q_subj)

    # parity: the unpacked layout IS the oracle — bit-for-bit over the
    # full batch — plus a sampled host-oracle cross-check
    oracle_match = all(
        np.array_equal(a, b) for a, b in zip(res_p, res_u)
    )
    from gochugaru_tpu.engine.oracle import SnapshotOracle, T

    so = SnapshotOracle(snap, {}, now_us=EPOCH)
    itn = snap.interner
    sample = rng.choice(B, 200, replace=False)
    host_ok = True
    for i in sample:
        rt, rid = itn.key_of(int(q_res[i]))
        st, sid = itn.key_of(int(q_subj[i]))
        want = so.check(rt, rid, "view", st, sid)
        d_i, p_i, o_i = res_p[0][i], res_p[1][i], res_p[2][i]
        if d_i and want != T:
            host_ok = False
        if not o_i and not p_i and want == T:
            host_ok = False
    oracle_match = bool(oracle_match and host_ok)
    note(f"oracle_match={oracle_match} (batch parity + {len(sample)} host samples)")

    try:
        rate_p = measured_rate_flat(eng_p, ds_p, slots, B, args_p)
    except RuntimeError as e:
        note(f"packed repeat harness: {e}")
        rate_p = 0.0

    reduction = bytes_u / max(bytes_p, 1)
    emit(
        "hbm_table_bytes_reduction", reduction, "x (unpacked/packed)",
        reduction / BYTES_BAR,
        edges=E, batch=int(B),
        table_bytes_packed=bytes_p, table_bytes_unpacked=bytes_u,
        table_bytes_per_edge=round(bytes_p / max(E, 1), 2),
        table_bytes_per_edge_unpacked=round(bytes_u / max(E, 1), 2),
        bytes_per_check=round(bpc_p, 1),
        bytes_per_check_unpacked=round(bpc_u, 1),
        oracle_match=oracle_match,
        note=f"bar {BYTES_BAR}x; est. gathered B/check {bpc_p:.0f} vs {bpc_u:.0f}",
    )
    ratio = (rate_p / rate_u) if rate_u else float("nan")
    # roofline columns for BOTH layouts: the packed layout's achieved
    # GB/s against the measured ceiling (and the unpacked comparison
    # point) — the A/B the silicon window asks of the decode layer
    rl_p = roofline_columns(rate_p, bytes_per_check=bpc_p)
    rl_u = roofline_columns(rate_u, bytes_per_check=bpc_u)
    emit(
        "hbm_packed_true_rate", rate_p, "checks/sec/chip",
        rate_p / NORTH_STAR_RATE,
        edges=E, batch=int(B), rate_basis="repeat-harness",
        unpacked_rate=round(rate_u, 1),
        vs_unpacked=round(ratio, 4) if rate_u else None,
        table_bytes_per_edge=round(bytes_p / max(E, 1), 2),
        **rl_p,
        achieved_gbps_unpacked=rl_u["achieved_gbps"],
        roofline_frac_unpacked=rl_u["roofline_frac"],
        oracle_match=oracle_match,
        note=(
            f"bar ≥{RATE_BAR:.0%} of unpacked"
            + ("" if not rate_u else f"; measured {ratio:.1%}")
        ),
    )

    # ---- pinned latency tier on the packed layout ----------------------
    SB = 2048
    dl, pl, ol = eng_p.check_columns_latency(
        ds_p, q_res[:SB].copy(), q_perm[:SB].copy(), q_subj[:SB].copy(),
        now_us=EPOCH,
    )
    assert np.array_equal(dl, res_p[0][:SB])
    assert np.array_equal(pl, res_p[1][:SB])
    note("latency-tier parity with throughput path: ok")
    try:
        emit_small_batch_row(
            "hbm_packed_small_batch_p99_latency", eng_p, ds_p,
            q_res[:SB].copy(), q_perm[:SB].copy(), q_subj[:SB].copy(),
            edges=E, now_us=EPOCH,
            table_bytes_per_edge=round(bytes_p / max(E, 1), 2),
        )
    except Exception as e:  # optional row must never cost the main ones
        note(f"small-batch latency row failed: {type(e).__name__}: {e}")

    # ---- routed partitioned serve on the packed layout -----------------
    del eng_u, ds_u, args_u, args_p
    try:
        import jax

        M = _ARGS.mesh
        if len(jax.devices()) < M:
            raise RuntimeError(
                f"{len(jax.devices())} devices < mesh {M}"
                " (run under XLA_FLAGS=--xla_force_host_platform_device_count)"
            )
        from gochugaru_tpu.engine.plan import EngineConfig
        from gochugaru_tpu.parallel import ShardedEngine, make_mesh

        cfg = EngineConfig.for_schema(cs, flat_packed=True)
        sharded = ShardedEngine(cs, make_mesh(1, M), cfg)
        t0 = time.perf_counter()
        ds_r = sharded.prepare_snapshot_partitioned(snap)
        note(f"routed partitioned prepare: {time.perf_counter() - t0:.1f}s")
        assert ds_r.flat_meta is not None and ds_r.flat_meta.packed
        RB = 4096
        dr, pr, orr = sharded.check_columns(
            ds_r, q_res[:RB], q_perm[:RB], q_subj[:RB], now_us=EPOCH
        )
        assert np.array_equal(np.asarray(dr), res_p[0][:RB])
        assert np.array_equal(np.asarray(pr), res_p[1][:RB])
        assert np.array_equal(np.asarray(orr), res_p[2][:RB])
        from gochugaru_tpu.engine.flat import PART_SHARDED_KEYS

        split = sum(
            int(getattr(ds_r.arrays[k], "nbytes", 0))
            for k in PART_SHARDED_KEYS if k in ds_r.arrays
        )
        whole = table_bytes(ds_r) - split
        per_dev = whole + split / M
        emit(
            "hbm_routed_partitioned_bytes_per_device", per_dev, "bytes",
            (bytes_p / max(per_dev, 1)),
            edges=E, batch=RB, mesh=f"1x{M}",
            vs_single_chip=round(per_dev / max(bytes_p, 1), 4),
            # the 1B/16 arithmetic inputs: whole-resident vs model-split
            # shares, per edge (BENCHMARKS.md "HBM-lean tables")
            whole_bytes_per_edge=round(whole / max(E, 1), 2),
            split_bytes_per_edge=round(split / max(E, 1), 2),
            oracle_match=True,
            note="routed serve on packed tables; parity vs single-chip packed",
        )
    except Exception as e:
        note(f"routed partitioned section skipped: {type(e).__name__}: {e}")


if __name__ == "__main__":
    from benchmarks.common import bench_main

    bench_main(main)
