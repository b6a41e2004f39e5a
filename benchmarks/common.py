"""Shared benchmark harness utilities.

Every benchmark prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is the fraction of the BASELINE.json north-star target
(10M checks/sec/chip or 2 ms p99) — the reference itself publishes no
numbers (BASELINE.md), so the target is the denominator.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Callable, Sequence

import numpy as np

NORTH_STAR_RATE = 10_000_000  # checks/sec/chip
NORTH_STAR_P99_MS = 2.0


def emit(
    metric: str, value: float, unit: str, vs_baseline: float, **extra
) -> None:
    """One JSON metric line.  ``extra`` carries measurement-context
    fields (edges, batch, ...) so a headline number can never silently
    describe a smaller world than its config names."""
    import jax

    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(float(value), 4),
                "unit": unit,
                "vs_baseline": round(float(vs_baseline), 4),
                # every row names the device it ran on: a CPU proxy
                # number must never read as a chip number
                "platform": jax.default_backend(),
                **extra,
            }
        )
    )


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr)


def time_steady(fn: Callable[[], object], reps: int = 5) -> float:
    """Steady-state seconds/call: warm once (compile), force the platform
    into synchronous execution with a real device→host fetch, then average
    individually-completed calls.

    Why the fetch: a real device→host transfer of one output proves the
    warm-up dispatches completed before the clock starts, independent of
    how a backend implements ``block_until_ready``."""
    import jax

    # warm THREE times, not one: the first dispatches after prepare also
    # fault in the freshly-built tables' pages (multi-GB at 10M+ edges),
    # which read as a ~3× slower "steady state" if timed
    for _ in range(3):
        out = fn()
        jax.block_until_ready(out)
    _force_sync_mode(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _force_sync_mode(out) -> None:
    """Fetch one full (unsliced) leaf of a jit output so subsequent
    blocked timings measure real execution."""
    import jax

    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        jax.device_get(leaves[0])


def repeat_harness(engine, iters: int):
    """Build a jitted fn running the engine's whole-batch check ``iters``
    times inside one ``lax.fori_loop`` dispatch, rotating the resource
    column every iteration (so XLA cannot hoist the loop body) and
    XOR/OR-accumulating the outputs (so it cannot dead-code them).

    Wraps the LEGACY two-phase kernel — the measured-true-rate baseline
    the round-2 verdict used; ``repeat_harness_flat`` is the production
    (flat hash-probe) counterpart with the same timing recipe.

    Timing recipe: t(2K) - t(K) cancels the fixed per-dispatch round trip,
    leaving K × the true batch evaluation time.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gochugaru_tpu.engine.device import _make_check_fn

    raw = _make_check_fn(
        engine.plan, engine.config, jit=False, caveat_plan=engine.caveat_plan
    )

    def fn(arrs, tid_map, now, u_subj, u_srel, u_wc, u_qctx,
           q_res, q_perm, q_subj, q_srel, q_wc, q_row, q_self, q_ctx, qctx):
        def body(i, carry):
            d0, p0, o0 = carry
            d, p, o = raw(
                arrs, tid_map, now, u_subj, u_srel, u_wc, u_qctx,
                jnp.roll(q_res, i), q_perm, q_subj, q_srel, q_wc,
                q_row, q_self, q_ctx, qctx,
            )
            return d0 ^ d, p0 ^ p, o0 | o
        z = jnp.zeros(q_res.shape[0], bool)
        return lax.fori_loop(0, iters, body, (z, z, z))

    return jax.jit(fn)


def repeat_harness_flat(engine, dsnap, slots, iters: int):
    """The repeat harness over the PRODUCTION (flat) kernel: ``iters``
    whole-batch evaluations inside one dispatch, resource column rotated
    per iteration, outputs XOR/OR-accumulated.  Same t(2K) - t(K) timing
    recipe as ``repeat_harness``; args come from
    DeviceEngine.flat_fn_and_args (pass ``jit=False`` there is not needed
    — the raw body is rebuilt here unjitted)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gochugaru_tpu.engine.flat import make_flat_fn

    raw = make_flat_fn(
        engine.compiled, engine.plan, engine.config, dsnap.flat_meta,
        tuple(slots), caveat_plan=engine.caveat_plan, jit=False,
    )

    def fn(arrs, tid_map, now, qm, qctx):
        def body(i, carry):
            d0, p0, o0 = carry
            d, p, o = raw(
                arrs, tid_map, now, qm.at[0].set(jnp.roll(qm[0], i)), qctx
            )
            return d0 ^ d, p0 ^ p, o0 | o
        z = jnp.zeros(qm.shape[1], bool)
        return lax.fori_loop(0, iters, body, (z, z, z))

    return jax.jit(fn)


def measured_rate_flat(engine, dsnap, slots, B: int, args, iters: int = 16) -> float:
    """True checks/sec of the flat kernel via the repeat harness:
    rate = iters·B / (t2 - t1).

    Raises RuntimeError when the t2 - t1 separation drowns in timing
    noise (small batches on a loaded host can invert the best-of-N
    samples, which would report a fantasy rate) — callers keep their
    blocked-dispatch figure instead of publishing garbage."""
    import jax

    f1 = repeat_harness_flat(engine, dsnap, slots, iters)
    f2 = repeat_harness_flat(engine, dsnap, slots, 2 * iters)
    out = f1(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(f2(*args))
    _force_sync_mode(out)

    def timed(f):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t1 = timed(f1)
    t2 = timed(f2)
    dt = t2 - t1
    if dt < 0.2 * max(t1, 1e-9):
        raise RuntimeError(
            f"repeat-harness timing unreliable: t1={t1*1000:.1f}ms "
            f"t2={t2*1000:.1f}ms — raise iters or quiet the host"
        )
    return iters * B / dt


def sync_rate(full_fn, null_fn, args, B: int, reps: int = 7):
    """True checks/sec on platforms where only synchronous-mode timing is
    real: force sync mode with one fetch, then time blocked executions of
    the real program and of a null program with identical input/output
    signature; the difference cancels the fixed per-dispatch round trip.
    Use a batch large enough that the true step dominates the ~2 ms timing
    noise on the fixed overhead.  Returns (rate, step_seconds,
    overhead_seconds)."""
    import jax

    out = full_fn(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(null_fn(*args))
    _force_sync_mode(out)

    def med(f):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t_null = med(null_fn)
    t_full = med(full_fn)
    step = max(t_full - t_null, 1e-9)
    return B / step, step, t_null


def measured_rate(engine, dsnap, B: int, args, iters: int = 16) -> float:
    """True checks/sec via the repeat harness: rate = iters·B / (t2 - t1)
    with t1 = one dispatch of `iters` loops, t2 = one of 2·iters."""
    import jax

    f1 = repeat_harness(engine, iters)
    f2 = repeat_harness(engine, 2 * iters)
    out = f1(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(f2(*args))
    _force_sync_mode(out)

    def timed(f):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = timed(f1)
    t2 = timed(f2)
    dt = max(t2 - t1, 1e-9)
    return iters * B / dt


def small_batch_latency(
    engine, dsnap, q_res, q_perm, q_subj, *,
    q_ctx=None, qctx_rows=None, now_us=None,
    warmup: int = 30, reps: int = 600,
    interleave_tracer=None, interleave=None,
) -> dict:
    """Warm latency-mode p50/p99 + mean per-stage budget for one small
    batch (engine/latency.py).  Every rep is a full dispatch — host
    lowering, H2D, pinned kernel, D2H — individually timed; the subject
    column rotates per rep so a platform cannot cache the answer.
    Returns a dict ready to splat into ``emit`` extra fields.

    Each rep roots a request-scoped trace span (utils/trace.py) exactly
    the way ``client.check`` does: with tracing disabled that is one
    branch returning the NOOP singleton, and with a tracer installed
    the rep pays full per-request span bookkeeping — so this helper is
    the honest subject for the tracing-overhead budget assertion
    (tests/test_trace_overhead.py).

    ``interleave_tracer`` (a ``trace.Tracer``) alternates that tracer
    in/out PER REP — adjacent reps see near-identical host conditions,
    so the off/on quantile differences measure tracing cost with the
    scheduler noise paired away (window-level A/B on a shared box
    drowns a <5% effect in drift).  Adds ``p50_ms_off``/``p50_ms_on``/
    ``p90_ms_off``/``p90_ms_on``/``p99_ms_off``/``p99_ms_on`` and
    ``delta_p50_ms``/``delta_p90_ms`` to the result; the headline
    quantiles then cover the mixed stream.

    ``interleave`` generalizes the same per-rep A/B to ANY toggle: an
    ``(on_fn, off_fn)`` pair called before each rep (odd reps on, even
    off) — the decision-provenance benches use it to price witness
    extraction (``lp.arm_witness``) and decision-log recording with the
    identical paired-noise methodology.  Mutually composable with
    ``interleave_tracer`` (both flip on the same rep parity)."""
    import jax  # noqa: F401  (ensures backend selection happened)

    from gochugaru_tpu.utils import trace as _trace

    lp = engine.latency_path(dsnap)
    B = q_res.shape[0]

    def once(i: int):
        sp = _trace.root_span("check", batch=B)
        try:
            out = lp.dispatch_columns(
                np.roll(q_res, i), q_perm, np.roll(q_subj, 2 * i),
                q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=now_us,
                span=sp,
            )
            assert out is not None, "latency path unavailable for this world"
            return out
        finally:
            sp.end()

    for i in range(warmup):
        if interleave is not None:
            # warm BOTH arms of the A/B (same parity as the measured
            # loop) so the no-recompile assertion can stay armed below
            interleave[0 if (i & 1) else 1]()
        once(i)
    if interleave is not None:
        interleave[1]()
    # frozen GC is the standard latency-service tuning (collection
    # pauses land straight in p99) — same recipe as bench1's client
    # loop, but unfrozen after the window: this helper runs MID-bench
    # and must not leave later sections with an uncollectable heap
    import gc

    gc.collect()
    gc.freeze()
    compiles_before = lp.compile_count
    ts = []
    by_mode = ([], [])  # interleave_tracer: (off reps, on reps)
    prev_tracer = _trace.get()
    stages = {"host_lower_s": 0.0, "h2d_s": 0.0, "kernel_s": 0.0, "d2h_s": 0.0}
    try:
        for i in range(reps):
            mode = i & 1
            if interleave_tracer is not None:
                _trace.install(interleave_tracer if mode else None)
            if interleave is not None:
                interleave[0 if mode else 1]()
            t0 = time.perf_counter()
            once(i)
            dt = (time.perf_counter() - t0) * 1000
            ts.append(dt)
            if interleave_tracer is not None or interleave is not None:
                by_mode[mode].append(dt)
            b = lp.last_budget
            for k in stages:
                stages[k] += getattr(b, k)
    finally:
        if interleave_tracer is not None:
            _trace.install(prev_tracer)
        if interleave is not None:
            interleave[1]()  # leave the toggle OFF
        gc.unfreeze()
    # armed for the interleave A/B too (both arms pre-warmed above): a
    # pin eviction mid-window would inject a compile rep into one arm
    # and silently corrupt the paired deltas — fail loudly instead
    assert lp.compile_count == compiles_before, (
        "latency path recompiled during the warm measurement window"
    )
    a = np.asarray(ts)
    out = {
        "p50_ms": round(float(np.percentile(a, 50)), 3),
        "p99_ms": round(float(np.percentile(a, 99)), 3),
        "mean_ms": round(float(a.mean()), 3),
        "host_ms": round(stages["host_lower_s"] / reps * 1000, 3),
        "h2d_ms": round(stages["h2d_s"] / reps * 1000, 3),
        "kernel_ms": round(stages["kernel_s"] / reps * 1000, 3),
        "d2h_ms": round(stages["d2h_s"] / reps * 1000, 3),
        "batch": int(B),
        "tier": int(lp.last_budget.tier),
        "n": int(reps),
    }
    if interleave_tracer is not None or interleave is not None:
        off, on = np.asarray(by_mode[0]), np.asarray(by_mode[1])
        for q in (50, 90, 99):
            out[f"p{q}_ms_off"] = round(float(np.percentile(off, q)), 3)
            out[f"p{q}_ms_on"] = round(float(np.percentile(on, q)), 3)
        out["delta_p50_ms"] = round(out["p50_ms_on"] - out["p50_ms_off"], 3)
        out["delta_p90_ms"] = round(out["p90_ms_on"] - out["p90_ms_off"], 3)
    return out


def emit_small_batch_row(
    metric: str, engine, dsnap, q_res, q_perm, q_subj, *,
    edges: int, q_ctx=None, qctx_rows=None, now_us=None, **extra
) -> dict:
    """Measure + emit one ``*_small_batch_p99_latency`` row with the
    host/H2D/kernel/D2H budget breakdown — the shared shape for the
    latency-mode rows of configs 1-4."""
    r = small_batch_latency(
        engine, dsnap, q_res, q_perm, q_subj,
        q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=now_us,
    )
    p99 = r.pop("p99_ms")
    emit(
        metric, p99, "ms", NORTH_STAR_P99_MS / max(p99, 1e-9),
        edges=int(edges), **r, **extra,
    )
    note(
        f"{metric}: B={r['batch']} (tier {r['tier']}) p50={r['p50_ms']}ms "
        f"p99={p99}ms | host={r['host_ms']} h2d={r['h2d_ms']} "
        f"kernel={r['kernel_ms']} d2h={r['d2h_ms']} (ms, mean)"
    )
    return {"p99_ms": p99, **r}


def latency_percentiles(
    fn: Callable[[], object], reps: int = 50
) -> tuple[float, float, float]:
    """(p50, p99, mean) milliseconds over individually-timed calls."""
    import jax

    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1000)
    a = np.asarray(ts)
    return float(np.percentile(a, 50)), float(np.percentile(a, 99)), float(a.mean())


def table_bytes(dsnap) -> int:
    """Resident device-table bytes of a DeviceSnapshot — delegates to
    the perf ledger (gochugaru_tpu/utils/perf.py), the ONE
    implementation bench columns and /perf share."""
    from gochugaru_tpu.utils.perf import table_bytes as _impl

    return _impl(dsnap)


def est_bytes_per_check(dsnap) -> float:
    """HBM bytes GATHERED per check: the perf ledger's meta-driven
    model (gochugaru_tpu/utils/perf.py gathered_bytes_model) — per
    table AND per recursion level (the old copy here admitted deeper
    recursion levels were excluded; the ledger computes them from the
    snapshot's measured arrow depth and rc geometry).  Row widths and
    lane dtypes come from the ACTUAL device arrays, so packed and
    unpacked layouts are compared by what truly crosses HBM — the
    roofline numerator next to checks/s."""
    from gochugaru_tpu.utils.perf import est_bytes_per_check as _impl

    return _impl(dsnap)


def roofline_columns(rate: float, dsnap=None, bytes_per_check=None) -> dict:
    """``achieved_gbps``/``roofline_frac`` bench columns: gathered
    bytes/check × measured true checks/s against the MEASURED bandwidth
    ceiling (perf.measure_bandwidth — triad microbench, cached per
    backend fingerprint).  Splat into ``emit`` extra fields next to any
    rate column."""
    from gochugaru_tpu.utils.perf import roofline_columns as _impl

    return _impl(rate, dsnap=dsnap, bytes_per_check=bytes_per_check)


def peak_rss_mb() -> float:
    """Per-process peak resident set in MiB (ru_maxrss ⊔ /proc VmHWM;
    gochugaru_tpu/utils/metrics.py) — benches attach it as a
    ``peak_rss_mb`` column so the host-sharded build's memory claim is a
    measured number riding the trajectory, not a docstring."""
    from gochugaru_tpu.utils.metrics import peak_rss_mb as _impl

    return _impl()


def join_lookup_prewarm(timeout: float = 300.0) -> None:
    """Measurement hygiene: a full prepare may spawn the lookup-prewarm
    thread (engine/device.py, walker-serving layouts only); on a
    one-core host its O(E log E) build steals ~half the core from the
    first seconds of any throughput window — join it (bounded) before
    timing anything.  Shared by bench3/bench4/bench8 instead of three
    copies of the loop."""
    import threading

    for t in threading.enumerate():
        if t.name == "gochugaru-lookup-prewarm":
            t.join(timeout=timeout)


def maybe_emit_metrics_snapshot() -> None:
    """Gated by GOCHUGARU_BENCH_METRICS=1 (run_all.py --metrics sets
    it): append one ``metrics_snapshot`` JSON line carrying the child's
    final ``metrics.default.snapshot()`` — so a bench regression row
    arrives WITH the counters that explain it (shed/retry/fallback/
    breaker activity, stage p99s), not just the headline number.
    Call as the last line of every bench main()."""
    import os

    if os.environ.get("GOCHUGARU_BENCH_METRICS") != "1":
        return
    from gochugaru_tpu.utils import metrics as _metrics

    snap = _metrics.default.snapshot()
    emit(
        "metrics_snapshot", len(snap), "keys", 0.0,
        snapshot={k: round(float(v), 9) for k, v in sorted(snap.items())},
    )


def bench_main(main) -> None:
    """Standard bench ``__main__`` tail: run ``main()`` and ALWAYS append
    the --metrics snapshot — a bench that dies mid-run would otherwise
    lose exactly the counter dump that explains the failure.  Exits with
    main's return code when it returns one (bench2's degraded-mesh rc)."""
    rc = None
    try:
        rc = main()
    finally:
        maybe_emit_metrics_snapshot()
    if isinstance(rc, int):
        raise SystemExit(rc)


def start_backend() -> str:
    """Place the compile cache and return the platform JAX found — a
    bench runs on whatever device is there (``JAX_PLATFORMS=cpu`` for a
    CPU proxy run) and never switches backend on its own."""
    import jax

    from gochugaru_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    return jax.default_backend()
