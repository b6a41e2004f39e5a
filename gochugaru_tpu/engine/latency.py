"""Latency-mode execution path: warm small-batch dispatch with pinned
kernels and an honest per-stage budget.

The throughput path (engine/device.py check_batch / check_columns) is
shaped for giant pipelined batches: pow2 padding that tracks the batch,
lazily-jitted kernels, results fetched whenever the async queue drains.
That is the right shape for 131k-item bulk scans and the wrong shape for
the other half of the north-star metric — p99 < 2 ms — which is a
property of *interactive-sized* dispatches (the small CheckBulkPermissions
batches of the reference, client/client.go:238-266), where any retrace,
fresh allocation, or stream hiccup lands directly in the tail.

This path removes every per-dispatch variable cost it can:

- **pinned executables**: the flat kernel is AOT-lowered and compiled
  ONCE per (snapshot geometry, permission slots, batch tier, qctx shape)
  and the ``Compiled`` object is called directly — a pinned executable
  structurally cannot retrace, so ``compile_count`` is an assertable
  invariant (tests/test_latency_path.py), not a hope.  Pins are shared
  engine-wide across delta revisions whose table shapes are unchanged.
- **batch tiers**: batches pad to a SMALL fixed ladder of tiers
  (EngineConfig.latency_tiers, default 256/1024/4096) instead of the
  batch's own pow2 — a workload whose batch size jitters between 900
  and 1100 stays on ONE pinned kernel.  The ladder is any sorted list
  of sizes, pow2 or not: the offline tuner (gochugaru_tpu/tune) fits
  tiers to the measured occupancy histogram, and pins are keyed by the
  tier value so a tuned (192, 576, 4096) ladder keeps the zero-retrace
  invariant.
- **preallocated staging**: one host-side query-matrix buffer per tier,
  refilled in place (engine/flat.py fill_qm) — steady-state dispatch
  allocates no host arrays; the context-free qctx device singleton is
  reused from the engine cache.
- **budget breakdown**: every dispatch is timed in four stages — host
  lowering (query packing), H2D (staging transfer), kernel (blocked
  execution), D2H (result fetch) — published through utils/metrics.py
  as ``latency.{host_lower,h2d,kernel,d2h,dispatch}_s`` with live
  p50/p99, and kept on ``last_budget`` for harnesses.  When the 2 ms
  budget is missed, the breakdown says which stage ate it.

Correctness contract is identical to the throughput path: returns the
same (definite, possible, overflow) planes; callers resolve conditional
and overflowed items on the host oracle.  Anything the path cannot serve
(no flat tables, too many distinct permissions, batch beyond the top
tier) returns None and the caller falls back — the latency path narrows
latency, never coverage.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..utils import faults
from ..utils import metrics as _metrics
from ..utils import perf as _perf
from ..utils import trace as _trace
from .flat import QM_ROWS, fill_qm


def tier_for(tiers, B: int) -> Optional[int]:
    """Smallest tier in the ladder holding ``B``, or None (→ the
    throughput path).  Shared by LatencyPath routing and the serving
    micro-batch former (serve/batcher.py), so "which pinned shape would
    this batch land on" has exactly one definition."""
    for t in sorted(tiers):
        if B <= t:
            return int(t)
    return None


@dataclass
class DispatchBudget:
    """Per-dispatch stage timings (seconds) of one latency-mode call."""

    batch: int
    tier: int
    host_lower_s: float
    h2d_s: float
    kernel_s: float
    d2h_s: float
    total_s: float
    #: True when this dispatch had to build a pinned executable (cold);
    #: warm steady-state dispatches are always False
    compiled: bool

    def as_dict(self) -> Dict[str, float]:
        return {
            "batch": self.batch,
            "tier": self.tier,
            "host_lower_s": self.host_lower_s,
            "h2d_s": self.h2d_s,
            "kernel_s": self.kernel_s,
            "d2h_s": self.d2h_s,
            "total_s": self.total_s,
            "compiled": self.compiled,
        }


class LatencyPath:
    """Warm small-batch dispatcher for one DeviceSnapshot.

    Obtained via ``DeviceEngine.latency_path(dsnap)`` (one per prepared
    snapshot; pinned executables are additionally shared engine-wide by
    shape fingerprint, so a Watch delta chain whose table geometry is
    stable re-pins without recompiling)."""

    def __init__(self, engine, dsnap, registry: Optional[Any] = None) -> None:
        self.engine = engine
        self.dsnap = dsnap
        self._m = registry or _metrics.default
        self._lock = threading.Lock()
        #: (slots, tier, qctx_key) → Compiled executable
        self._local: Dict[Tuple, Any] = {}
        #: tier → preallocated int32[QM_ROWS, tier] staging buffer
        self._qm_bufs: Dict[int, np.ndarray] = {}
        #: XLA compilations this path actually paid for (engine-cache
        #: misses) — the no-retrace assertion's subject
        self.compile_count = 0
        #: dispatches this path actually SERVED (not fallbacks) — the
        #: client reads it around check_batch to learn whether a
        #: latency-mode call really ran on this path (the breaker's
        #: half-open probe must not close on a silent batch fallback)
        self.dispatch_count = 0
        #: number of pinned-executable entries (incl. engine-cache hits)
        self.pin_count = 0
        #: (slots, tier, qctx_key) combos this path has SERVED warm — a
        #: fresh compile for a key already here means a pinned executable
        #: was lost (cache eviction, engine churn) and the "no retrace by
        #: construction" invariant is being paid for at serving time:
        #: fire a flight-recorder incident so the recompile is diagnosed
        #: from the traces around it, not discovered in a p99 regression
        self._served_keys: set = set()
        self.last_budget: Optional[DispatchBudget] = None
        self._shape_fp: Optional[Tuple] = None
        #: (clock value, device scalar) — the snapshot-relative clock has
        #: seconds resolution, so steady-state dispatch reuses one device
        #: scalar instead of paying a put per call
        self._now_cache: Optional[Tuple[int, Any]] = None
        #: (qctx device dict identity, shape key) — the context-free
        #: singleton is one stable dict, so its key derivation is free
        self._qctx_key_cache: Optional[Tuple[Any, Tuple]] = None
        #: lazily-computed gathered-bytes/check of this snapshot (the
        #: perf ledger's meta model) — sampled dispatch spans carry
        #: ``bytes_gathered_est`` without recomputing the model per call
        self._bpc_cache: Optional[float] = None
        #: decision-provenance witness extraction (engine/explain.py):
        #: armed, dispatches run the witness kernel variant (pinned under
        #: its own key — the disarmed pins are untouched) and the per-
        #: query winning-branch codes land on ``last_witness``.  Disarmed
        #: (default) the dispatch path pays ONE flag read; no witness
        #: buffer exists, no extra device output ships — the same
        #: zero-cost discipline as trace.NOOP
        self.witness_armed = False
        self.last_witness: Optional[np.ndarray] = None

    def _bytes_per_check(self) -> float:
        v = self._bpc_cache
        if v is None:
            try:
                v = _perf.est_bytes_per_check(self.dsnap)
            except Exception:
                v = 0.0
            self._bpc_cache = v
        return v

    # -- availability ----------------------------------------------------
    def tier_for(self, B: int) -> Optional[int]:
        """Smallest configured tier holding ``B``, or None (→ fall back
        to the throughput path)."""
        return tier_for(self.engine.config.latency_tiers, B)

    def arm_witness(self, on: bool = True) -> None:
        """Toggle witness extraction for subsequent dispatches.  Armed
        and disarmed executables pin under distinct keys, so flipping
        never evicts or retraces the other mode's pins — the first armed
        dispatch per (slots, tier, qctx shape) pays one compile, warm
        dispatches after that are pinned like any other."""
        self.witness_armed = bool(on)
        if not on:
            self.last_witness = None

    # -- pinning ---------------------------------------------------------
    def _fingerprint(self) -> Tuple:
        """Engine-wide pin-cache key component: the exact aval signature
        of the snapshot's device arrays.  Two snapshots with equal
        fingerprints (same FlatMeta, same padded shapes — the common
        case along a Watch delta chain) share pinned executables."""
        if self._shape_fp is None:
            self._shape_fp = tuple(
                sorted(
                    (k, tuple(v.shape), str(v.dtype))
                    for k, v in self.dsnap.arrays.items()
                )
            )
        return self._shape_fp

    def _pinned_for(self, slots, tier, qctx_key, args):
        """The pinned executable for this (slots, tier, qctx shape) —
        local-first, then the engine-wide cache, then a real compile.
        Witness-armed dispatches pin the witness kernel variant under a
        distinct key; disarmed keys are exactly the pre-witness ones."""
        wit = self.witness_armed
        key = (slots, tier, qctx_key) if not wit else (
            slots, tier, qctx_key, "wit"
        )
        fn = self._local.get(key)
        if fn is not None:
            return fn, False, key
        with self._lock:
            fn = self._local.get(key)
            if fn is not None:
                return fn, False, key
            full_key = (self.dsnap.flat_meta, self._fingerprint(), key)
            with self.engine._latency_pins_lock:
                fn = self.engine._latency_pins.get(full_key)
            fresh = fn is None
            if fresh:
                # the engine's jit, the one the throughput path calls:
                # the trace is reused, only the AOT compile at the
                # tier's shape is new
                jfn = self.engine._flat_fn_for(
                    slots, self.dsnap.flat_meta, witness=wit
                )
                fn = jfn.lower(*args).compile()
                self.compile_count += 1
                self._m.inc("latency.compiles")
                # device cost ledger: the Compiled is in hand, so the
                # XLA cost_analysis capture is free at pin time
                _perf.record_cost(
                    "latency_pin",
                    f"tier={tier};slots={slots}",
                    fn, self._m, tier=int(tier), slots=len(slots),
                )
                with self.engine._latency_pins_lock:
                    pins = self.engine._latency_pins
                    while len(pins) >= self.engine.LATENCY_PIN_CACHE_MAX:
                        pins.pop(next(iter(pins)))
                    pins[full_key] = fn
            self._local[key] = fn
            # same FIFO bound as the engine cache: varying qctx shapes
            # must not accumulate pinned executables without end
            while len(self._local) > self.engine.LATENCY_PIN_CACHE_MAX:
                self._local.pop(next(iter(self._local)))
            self.pin_count += 1
            return fn, fresh, key

    def _qm_buf(self, tier: int) -> np.ndarray:
        buf = self._qm_bufs.get(tier)
        if buf is None:
            buf = np.empty((QM_ROWS, tier), np.int32)
            self._qm_bufs[tier] = buf
        return buf

    # -- dispatch --------------------------------------------------------
    def dispatch(
        self,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        B: int,
        now,
        t_start: Optional[float] = None,
        span=_trace.NOOP,
    ):
        """One warm small-batch dispatch from already-lowered query
        columns.  ``now`` is the snapshot-relative int32 clock
        (snap.now_rel32).  ``t_start`` backdates the host-lowering stage
        to when the caller began lowering (so the budget charges query
        interning/packing honestly).  ``span`` is the request's trace
        span (utils/trace.py): a sampled dispatch records stage child
        spans rebuilt from the SAME perf_counter stamps the budget uses,
        so span durations and the ``latency.*`` stage timers agree
        exactly; the NOOP span allocates nothing.  Returns trimmed
        (d, p, ovf) numpy arrays, or None when this path cannot serve
        the batch."""
        import jax

        t0 = t_start if t_start is not None else time.perf_counter()
        meta = self.dsnap.flat_meta
        if meta is None or meta.sharded:
            # sharded tables need the shard_map kernel; the latency path
            # is a single-chip construct — callers fall back
            return None
        tier = self.tier_for(B)
        if tier is None:
            return None
        slots = tuple(
            sorted({int(s) for s in np.unique(queries["q_perm"]) if s >= 0})
        )
        if len(slots) > self.engine.config.flat_max_slots:
            return None
        # injection site AFTER the availability checks: a batch this path
        # would decline falls back without ever reaching the fault
        faults.fire("latency.dispatch")

        # ---- stage 1: host lowering (pack into the staging buffer) -----
        # the staging buffer is shared per tier: hold the path lock from
        # fill through upload so concurrent checkers can't corrupt it
        # (concurrent serving shards by path/thread; the lock only
        # covers the host-side window, not kernel execution)
        # each budget stage is also a leaf on the profiler's clock
        # (``gochugaru.engine.latency.*``, utils/trace.py); the budget's
        # own t0 reaches back over the caller's lowering, the ``fill``
        # annotation opens here, where ``engine.lower`` closed
        ann = _trace.annotation
        with self._lock:
            with ann("engine.latency.fill", span):
                qm = self._qm_buf(tier)
                fill_qm(queries, qm, meta)
                qctx_dev = self.engine._qctx_device(qctx)
                kc = self._qctx_key_cache
                if kc is not None and kc[0] is qctx_dev:
                    qctx_key = kc[1]
                else:
                    qctx_key = tuple(
                        (k, tuple(v.shape), str(v.dtype))
                        for k, v in sorted(qctx_dev.items())
                    )
                    self._qctx_key_cache = (qctx_dev, qctx_key)
                t1 = time.perf_counter()

            # ---- stage 2: H2D (staging buffer + clock scalar) ----------
            with ann("engine.latency.h2d", span):
                qm_dev = jax.device_put(qm)
                nc = self._now_cache
                if nc is not None and nc[0] == int(now):
                    now_dev = nc[1]
                else:
                    now_dev = jax.device_put(np.int32(now))
                    self._now_cache = (int(now), now_dev)
                if jax.default_backend() != "cpu":
                    # the fence is load-bearing off-CPU: the shared
                    # staging buffer must not be refilled (lock released)
                    # while an async H2D still reads it.  On CPU
                    # device_put copies synchronously and the fence
                    # itself costs ~0.3 ms a dispatch, so only there is
                    # it elided and the H2D remainder folds into the
                    # kernel stage
                    jax.block_until_ready((qm_dev, now_dev))
        t2 = time.perf_counter()

        # ---- stage 3: pinned kernel (blocked) --------------------------
        args = (self.dsnap.arrays, self.dsnap.tid_map, now_dev, qm_dev, qctx_dev)
        # served-key identity must carry the witness mode: the first
        # ARMED compile for a combo served warm disarmed is a new pin,
        # not a lost one — a false latency.retrace incident otherwise.
        # _pinned_for returns the key it resolved so the mutable
        # witness_armed flag is read exactly once per dispatch
        fn, fresh, pin_key = self._pinned_for(slots, tier, qctx_key, args)
        if fresh and pin_key in self._served_keys:
            # retrace detection: this exact shape was served warm before,
            # so the compile we just paid means its pin was evicted —
            # a silent tail regression in the making.  Counted + incident
            self._m.inc("latency.retraces")
            _trace.trigger_incident(
                "latency.retrace", tier=tier, batch=B, slots=len(slots),
            )
        with ann("engine.latency.kernel", span):
            out = fn(*args)
            jax.block_until_ready(out)
        t3 = time.perf_counter()

        # ---- stage 4: D2H readback -------------------------------------
        with ann("engine.latency.d2h", span):
            got = jax.device_get(out)
            if len(got) == 4:  # witness-armed kernel: fourth plane = codes
                d, p, ovf, w = got
                self.last_witness = w[:B]
            else:
                d, p, ovf = got
        t4 = time.perf_counter()

        budget = DispatchBudget(
            batch=B, tier=tier,
            host_lower_s=t1 - t0, h2d_s=t2 - t1,
            kernel_s=t3 - t2, d2h_s=t4 - t3,
            total_s=t4 - t0, compiled=fresh,
        )
        self.last_budget = budget
        self.dispatch_count += 1
        # pad-waste ledger: B live lanes padded to the tier — direct
        # calls and batcher-formed batches both flow through here, so
        # the serving occupancy is accounted per dispatch
        _perf.record_pad(tier, B, self._m)
        # wall-time ledger stages from the SAME t0..t4 stamps the budget
        # (and the stage spans below) subtract — one branch when no
        # measurement window is armed
        _perf.report_wall_stages(t0, t1, t2, t3, t4)
        if len(self._served_keys) < 4096:  # qctx-shape churn backstop
            self._served_keys.add(pin_key)
        m = self._m
        m.inc("latency.dispatches")
        if not fresh:
            # the dispatch p99 is the serving SLO: a cold compile is a
            # separate (counted) event, not a tail sample — and the
            # compile lands inside the kernel-stage window, so the stage
            # samples skip cold dispatches for the same reason
            m.observe("latency.host_lower_s", budget.host_lower_s)
            m.observe("latency.h2d_s", budget.h2d_s)
            m.observe("latency.kernel_s", budget.kernel_s)
            m.observe("latency.d2h_s", budget.d2h_s)
            m.observe("latency.dispatch_s", budget.total_s)
        if span.sampled:
            # stage spans from the SAME t0..t4 stamps the budget (and so
            # the latency.* timers) subtracted — durations agree exactly
            lsp = span.child(
                "latency.dispatch", t=t0,
                batch=B, tier=tier, compiled=fresh,
                pad_fraction=round(1.0 - B / tier, 4),
                bytes_gathered_est=round(self._bytes_per_check() * B, 1),
            )
            lsp.child_at("stage.host_lower", t0).end(t=t1)
            lsp.child_at("stage.h2d", t1).end(t=t2)
            lsp.child_at("stage.kernel", t2).end(t=t3)
            lsp.child_at("stage.d2h", t3).end(t=t4)
            lsp.end(t=t4)
        return d[:B], p[:B], ovf[:B]

    def dispatch_columns(
        self,
        q_res: np.ndarray,
        q_perm: np.ndarray,
        q_subj: np.ndarray,
        *,
        q_srel: Optional[np.ndarray] = None,
        q_wc: Optional[np.ndarray] = None,
        q_ctx: Optional[np.ndarray] = None,
        qctx_rows=None,
        now_us: Optional[int] = None,
        span=_trace.NOOP,
    ):
        """Latency-path bulk check from pre-interned int32 columns (the
        columnar mirror of the Relationship path; benches and tests call
        this).  Returns (d, p, ovf) or None → caller falls back."""
        t0 = time.perf_counter()
        queries, qctx = self.engine._columns_preamble(
            self.dsnap, q_res, q_perm, q_subj, q_srel, q_wc, q_ctx, qctx_rows,
            span=span,
        )
        now = self.dsnap.snapshot.now_rel32(now_us)
        return self.dispatch(
            queries, qctx, q_res.shape[0], now, t_start=t0, span=span
        )
