"""Masked frontier SpMV: LookupResources/LookupSubjects on the device.

The host walker (engine/lookup.py) answers the inverse-of-Check
questions by sorting transposed O(E) views on the host and running a
numpy worklist — O(E log E) cold start per snapshot and per-hop host
work proportional to the touched edges.  This module replaces that with
the GraphBLAS push idiom (RedisGraph, arXiv:1905.01294; Graphulo's
tables-as-matrices framing, arXiv:1609.08642) over the reverse-CSR
tables built alongside the forward layout (engine/rev.py):

- the frontier is a set of packed keys (k2 = (subject, srel1) for
  reverse reachability; k1 = (slot, resource) forward; child nodes for
  arrow traversal);
- one hop = one vectorized probe kernel (hash bucket + short in-bucket
  bisect finds each key's contiguous run) + budgeted emission kernels
  (a fixed-shape chunk of matching rows per dispatch, whatever the
  fan-out — the SpMV "gather" with the frontier as the mask);
- caveats/expirations filter the frontier IN the emission kernel via
  the same packed decode layer the Check kernel uses
  (engine/packed.py decode_block): an expired edge, or a caveated edge
  with no stored context (conditional-by-construction, and conditional
  results are omitted from lookups — the bool collapse), never leaves
  the device;
- the host only dedups (bitmap seen-sets), applies the schema-level
  worklist rules (membership-chain keys, permission-userset chains,
  wildcard handling — mirroring the walker's proven superset
  discipline), and streams candidate blocks to the exact filter.

Candidates stream in DETERMINISTIC discovery order (device kernels are
deterministic, host dedup is order-stable), which is what makes the
cursor contract exact: a ``LookupCursor`` pins (revision, query
fingerprint, results emitted) and a resume either continues the cached
live stream or deterministically recomputes and skips — no duplicate
and no lost IDs across page boundaries (tests/test_lookup_stream.py).

Eligibility: full prepares with the reverse index (FlatMeta.has_rev)
and no LSM delta level — delta chains keep the walker, whose
advance_lookup_index machinery is already delta-exact.  The sharded
stacked layout routes each hop's frontier to owner shards
(parallel/sharded.py lookup support) and only owner-crossing IDs move.
"""

from __future__ import annotations

import base64
import json
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..utils import faults, metrics
from ..utils import trace as _trace
from .hash import _ceil_pow2, mix32, take_in_bounds
from .rev import REV_TABLES, row_lanes

_mt = metrics.default

#: continuation cache per DeviceSnapshot (live candidate streams keyed
#: by cursor token; LRU — an evicted stream resumes by deterministic
#: recompute-and-skip)
_STREAM_CACHE_MAX = 16


# ---------------------------------------------------------------------------
# cursors
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LookupCursor:
    """Revision-pinned resumable position in one lookup's result stream.

    ``pos`` counts RESULTS already emitted (not candidates): the stream
    is deterministic per (snapshot revision, query, evaluation time), so
    skipping ``pos`` results reproduces the exact continuation even with
    no server-side state.  ``now_us`` pins that evaluation time: a
    caller who never passed one gets wall clock resolved ONCE at stream
    creation — a recompute-resume at a later wall clock would otherwise
    re-evaluate expiry gates and silently lose/duplicate IDs."""

    revision: int
    token: str  # query fingerprint — a cursor never resumes a different query
    pos: int
    now_us: Optional[int] = None

    def encode(self) -> str:
        raw = json.dumps(
            {"r": self.revision, "t": self.token, "p": self.pos,
             "n": self.now_us},
            separators=(",", ":"),
        ).encode()
        return base64.urlsafe_b64encode(raw).decode()

    @staticmethod
    def decode(s: str) -> "LookupCursor":
        from ..utils.errors import PreconditionFailedError

        try:
            d = json.loads(base64.urlsafe_b64decode(s.encode()))
            n = d.get("n")
            return LookupCursor(
                int(d["r"]), str(d["t"]), int(d["p"]),
                int(n) if n is not None else None,
            )
        except Exception as e:
            raise PreconditionFailedError(f"malformed lookup cursor: {e}")


def query_token(*parts) -> str:
    """Stable query fingerprint for cursor validation."""
    import hashlib

    h = hashlib.sha1("\x1f".join(str(p) for p in parts).encode()).hexdigest()
    return h[:16]


def resolve_now_us(cursor: Optional["LookupCursor"],
                   now_us: Optional[int]) -> int:
    """The lookup's pinned evaluation time: an explicit ``now_us`` wins,
    a resuming cursor reuses the one its stream was created with, and a
    fresh implicit-time lookup resolves wall clock ONCE — so
    recompute-resumes re-evaluate expiry/caveat gates at the SAME
    instant and the no-dup/no-loss contract holds."""
    import time as _time

    if now_us is not None:
        return int(now_us)
    if cursor is not None and cursor.now_us is not None:
        return int(cursor.now_us)
    return int(_time.time() * 1_000_000)


# ---------------------------------------------------------------------------
# host-side seen-sets (bitmaps; order-stable dedup)
# ---------------------------------------------------------------------------


class _Seen:
    """Bitmap over a dense int domain; ``fresh`` returns the sorted
    unique not-yet-seen subset and marks it."""

    def __init__(self, domain: int) -> None:
        self._bm = np.zeros((max(domain, 1) + 7) >> 3, np.uint8)

    def fresh(self, ids: np.ndarray) -> np.ndarray:
        if ids.size == 0:
            return ids.astype(np.int64)
        ids = np.unique(ids.astype(np.int64))
        byte = ids >> 3
        bit = (1 << (ids & 7)).astype(np.uint8)
        take = (self._bm[byte] & bit) == 0
        ids, byte, bit = ids[take], byte[take], bit[take]
        if ids.size:
            # two fresh ids can share a byte: sorted ids put them in one
            # run — OR-reduce per distinct byte, then one plain scatter
            # (np.bitwise_or.at is ~50x slower than this at volume)
            ub, first = np.unique(byte, return_index=True)
            self._bm[ub] |= np.bitwise_or.reduceat(bit, first)
        return ids


#: bitmap byte budget per seen-set — worlds whose key domain would need
#: more fall back to the host walker
_SEEN_BUDGET_BYTES = 1 << 27


# ---------------------------------------------------------------------------
# device kernels (per-FlatMeta, cached on the engine)
# ---------------------------------------------------------------------------


def _field0_reader(spec, w: int):
    """Reader of column 0 at row indices (the bisect compare): packed
    specs decode just the lanes field 0 lives in — same shift/mask
    decode the Check kernel fuses into its gathers.

    It never reshapes the table: the enumeration tables arrive flat
    (engine/rev.py's shipped layout, row r at lanes [r·stride, …)), so
    row r's field 0 is a gather at ``r·stride``.  Flattening an
    ``[n, w]`` operand here instead would ask the TPU for a physical
    copy of the whole table — once a round inside the fused loop
    (engine/spmm.py), once a looped hop."""
    import jax.numpy as jnp

    stride = row_lanes(spec, w)
    if spec is None:
        return lambda tbl, idx: take_in_bounds(tbl, idx * stride)

    bits, base, delta_of, dict_id, off_bit = spec[2][0]
    assert off_bit == 0 and delta_of < 0 and dict_id < 0, (
        "reverse-index key columns are plain ranges at bit 0"
    )

    def rd(tbl, idx):
        at = idx * stride
        v = take_in_bounds(tbl, at).astype(jnp.int32)
        if bits > 16:
            v = v | (take_in_bounds(tbl, at + 1).astype(jnp.int32) << 16)
        if bits < 32:
            v = v & jnp.int32((1 << bits) - 1)
        return v + jnp.int32(base) if base else v

    return rd


def _rows_reader(spec, w: int, flat: bool):
    """Reader of whole decoded rows (int32[..., w]) at row indices.  A
    flat enumeration table gathers each row's lanes by flat index and
    stacks the small block; a ``[n, w]`` table (``arx``, which the
    Check kernel shares) gathers rows."""
    import jax.numpy as jnp

    dec = _decoder(spec)
    if not flat:
        return lambda tbl, ridx: dec(take_in_bounds(tbl, ridx))
    stride = row_lanes(spec, w)

    def rd(tbl, ridx):
        lanes = ridx[..., None] * stride + jnp.arange(stride, dtype=jnp.int32)
        return dec(take_in_bounds(tbl, lanes))

    return rd


def _decoder(spec):
    if spec is None:
        return lambda blk: blk

    from .packed import decode_block

    return lambda blk: decode_block(blk, spec)


class FrontierKernels:
    """The jitted probe/emit kernels of one FlatMeta geometry (cached on
    the engine keyed by meta — delta-free full prepares with the same
    geometry share compiled programs)."""

    def __init__(self, meta, config) -> None:
        import jax

        self.meta = meta
        self.CH = int(config.lookup_chunk)
        self.F_min = int(config.lookup_frontier_min)
        self._pk = dict(meta.packed)
        self._pko = dict(meta.packed_off)
        e_gates = (["cav", "ctx"] if meta.e_hascav else []) + (
            ["exp"] if meta.e_hasexp else []
        )
        ar_gates = (["cav", "ctx"] if meta.ar_hascav else []) + (
            ["exp"] if meta.ar_hasexp else []
        )
        self.w_rv = 2 + len(e_gates)
        self.w_ra = 2 + len(ar_gates)
        #: raw (unjitted) bodies — the sharded engine shard_maps these
        #: over the model axis verbatim: inside a shard the off/table
        #: BLOCKS have exactly the single-shard shapes, so one body
        #: serves both layouts (parallel/sharded.py lookup hops)
        self.raw_runs = {
            "rv": self._make_runs("rvx", "rv_off", meta.rv_cap, self.w_rv),
            "ra": self._make_runs("rax", "ra_off", meta.ra_cap, self.w_ra),
        }
        self.raw_emits = {
            "rv": self._make_emit("rvx", self.w_rv, 2, meta.e_hascav,
                                  meta.e_hasexp),
            "ra": self._make_emit("rax", self.w_ra, 2, meta.ar_hascav,
                                  meta.ar_hasexp),
        }
        if meta.has_fw:
            self.raw_runs["fw"] = self._make_runs(
                "fwx", "fw_off", meta.fw_cap, self.w_rv
            )
            self.raw_emits["fw"] = self._make_emit(
                "fwx", self.w_rv, 2, meta.e_hascav, meta.e_hasexp
            )
        # forward arrows ride the EXISTING argx/arx range view
        self._arg_aligned = "argx" in {k for k, _w, _c in meta.aligned}
        self.raw_runs["arg"] = self._make_runs_group()
        w_arx = 1 + len(ar_gates)
        self.raw_emits["arg"] = self._make_emit(
            "arx", w_arx, 1, meta.ar_hascav, meta.ar_hasexp
        )
        self._runs = {k: jax.jit(v) for k, v in self.raw_runs.items()}
        # the chunk size is a static arg: emission kernels compile per
        # pow2 chunk tier, so a 200-row hop costs O(256) work, not
        # O(lookup_chunk) — the fixed budget only caps the LARGEST tier
        self._emits = {
            k: jax.jit(v, static_argnums=5) for k, v in self.raw_emits.items()
        }
        # fused hop: probe + FIRST emission chunk in one compiled
        # program — most hops emit fewer than CH0 rows, so the common
        # case is one dispatch + one fetch per hop instead of two of
        # each (the per-dispatch fixed cost is the frontier's floor on
        # gather-poor hosts)
        self.CH0 = min(4096, self.CH)
        self._hops_fused = {
            k: self._make_hop(k) for k in self.raw_runs if k != "arg"
        }
        if not self._arg_aligned:
            self._hops_fused["arg"] = self._make_hop("arg")
        #: (kind, frontier-pad) shapes already registered with the perf
        #: cost ledger — the hot hop path checks this local set only
        self._cost_reg: set = set()

    def _make_hop(self, kind: str):
        import jax
        import jax.numpy as jnp

        runs_raw = self.raw_runs[kind]
        emit_raw = self.raw_emits[kind]
        CH0 = self.CH0

        def fn(off, off_a, tbl, emit_tbl, keys, now):
            lo, ln = runs_raw(off, off_a, tbl, keys)
            rows, live = emit_raw(emit_tbl, lo, ln, jnp.int32(0), now, CH0)
            return lo, ln, rows, live

        return jax.jit(fn)

    # -- offset reads (anchor+residual when packed) ----------------------
    def _off_reader(self, off_key: str):
        import jax.numpy as jnp

        shift = self._pko.get(off_key)

        def rd(off, off_a, idx):
            if shift is None:
                return take_in_bounds(off, idx)
            return take_in_bounds(off_a, idx >> shift) + take_in_bounds(
                off, idx
            ).astype(jnp.int32)

        return rd

    # -- point-run probe: hash bucket + in-bucket bisect ------------------
    def _make_runs(self, tbl_key: str, off_key: str, cap: int, w: int):
        import jax.numpy as jnp

        steps = max(int(cap).bit_length(), 1)
        spec = self._pk.get(tbl_key)
        col0 = _field0_reader(spec, w)
        stride = row_lanes(spec, w)
        offr = self._off_reader(off_key)

        def fn(off, off_a, tbl, keys):
            size = (off.shape[0] - 1)  # single-shard layout (M=1)
            h = (mix32([keys], jnp) & jnp.uint32(size - 1)).astype(jnp.int32)
            start = offr(off, off_a, h)
            end = offr(off, off_a, h + 1)
            last = tbl.shape[0] // stride - 1  # rows of the flat table

            def bisect(left: bool):
                lo = start
                n = end - start
                for _ in range(steps):
                    # n == 0 must freeze: an unguarded step would read
                    # past the bucket end (the next bucket's rows — or
                    # pad) and walk lo out of the run
                    alive = n > 0
                    half = n >> 1
                    mid = lo + half
                    v = col0(tbl, jnp.clip(mid, 0, last))
                    go = alive & ((v < keys) if left else (v <= keys))
                    lo = jnp.where(go, mid + 1, lo)
                    n = jnp.where(go, n - half - 1, jnp.where(alive, half, 0))
                return lo

            lo = bisect(True)
            ln = bisect(False) - lo
            dead = keys < 0
            return jnp.where(dead, 0, lo), jnp.where(dead, 0, ln)

        return fn

    # -- group-table probe (argx range view: hash probe or aligned ladder)
    def _make_runs_group(self):
        import jax.numpy as jnp

        meta = self.meta
        al = {k: (w, caps) for k, w, caps in meta.aligned}
        dec = _decoder(self._pk.get("argx"))
        if "argx" in al:
            from .hash import probe_aligned

            w_log, caps = al["argx"]
            spec = self._pk.get("argx")
            w_eff = spec[1] if spec is not None else w_log

            def fn(tbls, keys):
                blk = dec(probe_aligned(tbls, caps, w_eff, (keys,)))
                hit = (blk[..., 0] == keys[..., None]) & (keys >= 0)[..., None]
                lo = jnp.sum(jnp.where(hit, blk[..., 1], 0), axis=-1)
                hi = jnp.sum(jnp.where(hit, blk[..., 2], 0), axis=-1)
                return lo, hi - lo

            return fn

        from .hash import slice_blocks

        offr = self._off_reader("arr_off")
        cap = meta.arr_cap

        def fn2(off, off_a, gx, keys):
            size = off.shape[0] - 1
            h = (mix32([keys], jnp) & jnp.uint32(size - 1)).astype(jnp.int32)
            start = offr(off, off_a, h)
            blk = dec(slice_blocks(gx, start, cap))
            hit = (blk[..., 0] == keys[..., None]) & (keys >= 0)[..., None]
            lo = jnp.sum(jnp.where(hit, blk[..., 1], 0), axis=-1)
            hi = jnp.sum(jnp.where(hit, blk[..., 2], 0), axis=-1)
            return lo, hi - lo

        return fn2

    # -- budgeted emission: one fixed-shape chunk of matching rows --------
    def _make_emit(self, tbl_key: str, w: int, gate_at: int, hascav: bool,
                   hasexp: bool):
        import jax.numpy as jnp
        from jax import lax

        rows_at = _rows_reader(
            self._pk.get(tbl_key), w, tbl_key in REV_TABLES
        )

        def fn(tbl, lo, ln, chunk0, now, CH: int):
            chunk0 = jnp.asarray(chunk0).reshape(-1)[0]
            F = lo.shape[0]
            cum = jnp.cumsum(ln)
            cumstart = cum - ln
            total = cum[F - 1] if F else jnp.int32(0)
            pos = chunk0 + jnp.arange(CH, dtype=jnp.int32)
            valid = pos < total
            # key index per slot: scatter each in-window run start (runs
            # are disjoint, nonzero runs have unique starts), then a
            # running max — O(F + CH), no per-slot binary search
            rel = cumstart - chunk0
            inw = (rel > 0) & (rel < CH) & (ln > 0)
            sidx = jnp.where(inw, rel, CH)  # CH = dropped
            marks = jnp.full(CH, -1, jnp.int32).at[sidx].max(
                jnp.arange(F, dtype=jnp.int32), mode="drop"
            )
            base = jnp.max(
                jnp.where((ln > 0) & (cumstart <= chunk0),
                          jnp.arange(F, dtype=jnp.int32), -1)
            )
            marks = marks.at[0].max(base)
            ki = lax.cummax(marks)
            kic = jnp.clip(ki, 0, max(F - 1, 0))
            ok = valid & (ki >= 0)
            ridx = take_in_bounds(lo, kic) + pos - take_in_bounds(
                cumstart, kic
            )
            ridx = jnp.where(ok, ridx, 0)
            rows = rows_at(tbl, ridx)
            live = ok
            if hasexp:
                exp = rows[..., gate_at + (2 if hascav else 0)]
                live = live & ((exp == 0) | (exp > now))
            if hascav:
                # a caveated edge with stored context can still be
                # DEFINITE (the CEL VM resolves it); only the
                # conditional-by-construction case (no stored context —
                # lookups carry no request context) filters here
                cav = rows[..., gate_at]
                ctx = rows[..., gate_at + 1]
                live = live & ((cav == 0) | (ctx >= 0))
            return rows, live

        return fn

    # -- host-callable wrappers ------------------------------------------
    def pad_keys(self, keys: np.ndarray) -> np.ndarray:
        F = _ceil_pow2(max(keys.shape[0], 1), self.F_min)
        out = np.full(F, -1, np.int32)
        out[: keys.shape[0]] = keys
        return out

    def runs(self, kind: str, args: Tuple, keys: np.ndarray):
        """(lo, ln, total) device handles + host total for padded keys."""
        faults.fire("lookup.dispatch")
        _mt.inc("lookup.dispatches")
        _trace.count_if_active("dispatches")
        sp = _trace.current()
        import jax.numpy as jnp

        with _trace.stage("lookup.args", sp):
            kp = self.pad_keys(keys)
            if kind == "arg" and self._arg_aligned:
                args = (tuple(args),)
            self._register_cost(kind, self._runs[kind], (*args, kp))
            kp = jnp.asarray(kp)
        with _trace.stage("lookup.hop.enqueue", sp):
            lo, ln = self._runs[kind](*args, kp)
        with _trace.stage("lookup.hop.fetch", sp):
            total = int(np.asarray(ln).sum())
        return lo, ln, total

    def _register_cost(
        self, kind: str, fn, call_args: Tuple, F: Optional[int] = None
    ) -> None:
        """Lazy cost-ledger registration for one frontier kernel shape
        (kernel-cache time, realized only on explicit demand).  The
        per-kernels ``_cost_reg`` set makes the steady-state hop path
        one local set-lookup — no global ledger lock, no meta hash, no
        key formatting per hop."""
        if F is None:
            F = int(call_args[-1].shape[0])
        if (kind, F) in self._cost_reg:
            return
        self._cost_reg.add((kind, F))
        from ..utils import perf as _perf

        key = f"{kind};F={F};meta={hash(self.meta) & 0xFFFFFFFF:08x}"
        _perf.register_cost_thunk(
            "spmv", key,
            lambda fn=fn, avals=_perf.avals_of(call_args): fn.lower(
                *avals
            ).compile(),
        )

    def emit(self, kind: str, tbl, lo, ln, chunk0: int, now,
             ch: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        import jax
        import jax.numpy as jnp

        _mt.inc("lookup.dispatches")
        _trace.count_if_active("dispatches")
        sp = _trace.current()
        with _trace.stage("lookup.args", sp):
            chunk0 = jnp.int32(chunk0)
        with _trace.stage("lookup.hop.enqueue", sp):
            out = self._emits[kind](tbl, lo, ln, chunk0, now, ch or self.CH)
        with _trace.stage("lookup.hop.fetch", sp):
            rows, live = jax.device_get(out)
        return rows, live

    def _tier(self, n: int) -> int:
        return min(_ceil_pow2(max(n, 1), 256), self.CH)

    def expand(self, kind: str, args: Tuple, tbl, keys: np.ndarray, now):
        """Full budgeted expansion of ``keys`` over one view: yields
        (rows int32[n, w], already live-filtered) per chunk.  ``args``
        is the probe argument tuple (incl. the rows table); ``tbl`` the
        rows table the emission gathers from."""
        import jax
        import jax.numpy as jnp

        if keys.shape[0] == 0:
            return
        fused = self._hops_fused.get(kind)
        _mt.inc("lookup.hops")
        _trace.count_if_active("hops")
        sp = _trace.current()
        if fused is not None:
            faults.fire("lookup.dispatch")
            _mt.inc("lookup.dispatches")
            _trace.count_if_active("dispatches")
            with _trace.stage("lookup.args", sp):
                kp = self.pad_keys(keys)
                self._register_cost(
                    f"hop:{kind}", fused,
                    (args[0], args[1], args[2], tbl, kp,
                     now if hasattr(now, "dtype") else jnp.int32(now)),
                    F=int(kp.shape[0]),
                )
                kp = jnp.asarray(kp)
            with _trace.stage("lookup.hop.enqueue", sp):
                lo, ln, rows, live = fused(
                    args[0], args[1], args[2], tbl, kp, now
                )
            with _trace.stage("lookup.hop.fetch", sp):
                ln_h, rows, live = jax.device_get((ln, rows, live))
            with _trace.stage("lookup.expand", sp):
                total = int(ln_h.sum())
                rows = rows[live]
            yield rows
            at = self.CH0
        else:
            lo, ln, total = self.runs(kind, args, keys)
            at = 0
        while at < total:
            ch = self._tier(total - at)
            rows, live = self.emit(kind, tbl, lo, ln, at, now, ch)
            with _trace.stage("lookup.expand", sp):
                rows = rows[live]
            yield rows
            at += ch


def kernels_for(engine, meta) -> FrontierKernels:
    cache = engine.__dict__.setdefault("_spmv_kernels", {})
    k = cache.get(meta)
    if k is None:
        k = FrontierKernels(meta, engine.config)
        while len(cache) >= 8:
            cache.pop(next(iter(cache)))
        cache[meta] = k
    return k


# ---------------------------------------------------------------------------
# per-snapshot frontier state (dense maps, table arg tuples)
# ---------------------------------------------------------------------------


def frontier_static_ok(meta, snap) -> bool:
    """The STATIC half of frontier eligibility — reverse index present
    and the seen-set bitmap domains fit budget.  Shared with the
    prewarm decision (engine/device.py): a snapshot failing this always
    walker-serves, so it wants the background transposed-index build."""
    if meta is None or not meta.has_rev:
        return False
    NS1 = meta.N * meta.S1
    NSr = meta.N * (max(snap.num_slots, 1) + 1)  # raw pair bitmap domain
    return max(NS1, NSr) <= _SEEN_BUDGET_BYTES * 8


def frontier_ok(engine, dsnap) -> bool:
    """Device frontier eligibility: the static half plus the
    per-revision conditions — no LSM delta level riding (the walker's
    advance machinery is the delta-exact path), and sharded snapshots
    only when the engine has the owner-routed hop path."""
    meta = dsnap.flat_meta
    if not frontier_static_ok(meta, dsnap.snapshot):
        return False
    if meta.delta is not None:
        return False
    if meta.sharded and not hasattr(engine, "lookup_hops_for"):
        return False
    return True


class FrontierState:
    """Per-DeviceSnapshot lookup server: dense slot maps, device table
    argument tuples, and the candidate-stream generators (cached on the
    snapshot via ``state_for``)."""

    def __init__(self, engine, dsnap) -> None:
        import jax.numpy as jnp

        self.engine = engine
        self.dsnap = dsnap
        self.meta = meta = dsnap.flat_meta
        self.kern = kernels_for(engine, meta)
        self.snap = snap = dsnap.snapshot
        self.N = meta.N
        self.S1 = meta.S1
        self.logN = self.N.bit_length() - 1
        from .flat import _dense_np

        self.k1d = _dense_np(meta.k1_dense)  # raw slot → dense k1 (-1 = none)
        self.k2d = _dense_np(meta.k2_dense)
        n_k1 = int(self.k1d.max()) + 1 if self.k1d.size else 0
        self.k1_raw = np.full(max(n_k1, 1), -1, np.int32)
        for raw, d in enumerate(self.k1d):
            if d >= 0:
                self.k1_raw[d] = raw
        # dense k1 slot → (dense k2 of the same raw slot) + 1; 0 = the
        # relation is never a userset target, so no membership-chain key
        self.k2p1_of_k1d = np.zeros(max(n_k1, 1), np.int64)
        for d in range(n_k1):
            raw = self.k1_raw[d]
            if raw >= 0 and self.k2d[raw] >= 0:
                self.k2p1_of_k1d[d] = int(self.k2d[raw]) + 1
        # -- schema-level type-safety pruning (the big frontier lever) --
        # a userset (t, r) can only ever BE a subject where the schema
        # declares ``t#r`` as an allowed subject form, and a node can
        # only be an arrow CHILD if its type is a declared direct
        # subject of some tupleset relation — so chain keys / reverse-
        # arrow probes for other (type, slot) combinations are
        # structurally dead and never reach the device.  Without this a
        # 100k-candidate hop probes 100k impossible keys (Zanzibar's
        # type safety, applied as frontier pruning)
        compiled = snap.compiled
        interner = snap.interner
        num_slots = max(compiled.num_slots, 1)
        n_types = max(interner.num_types, 1)
        self.chain_ok = np.zeros((n_types + 1, self.S1 + 1), bool)
        self.child_ok = np.zeros(n_types + 1, bool)
        self.slot_of_type = np.zeros((n_types + 1, num_slots), bool)
        tname_of_tid = {tid: t for t, tid in compiled.type_ids.items()}
        for tname, tid in compiled.type_ids.items():
            itid = interner.type_lookup(tname)
            ct = compiled.types[tid]
            if itid >= 0:
                self.slot_of_type[itid, sorted(ct.relations)] = True
            for slot, relation in ct.relations.items():
                is_ts = slot in compiled.tupleset_slots
                for a in relation.allowed:
                    a_itid = interner.type_lookup(tname_of_tid[a.type_id])
                    if a_itid < 0:
                        continue
                    if a.relation_slot >= 0:
                        d = self.k2d[a.relation_slot]
                        if d >= 0:
                            self.chain_ok[a_itid, d + 1] = True
                    elif is_ts:
                        self.child_ok[a_itid] = True
        # permission slots per interner type id, dense-k2 + declared-
        # subject-form filtered (the permission-userset chain)
        self.perm_chains = bool(compiled.has_permission_usersets)
        self.perm_k2p1_of_tid: Dict[int, np.ndarray] = {}
        tbl = np.zeros((n_types, num_slots), bool)
        for tname, d in compiled.schema.definitions.items():
            itid = interner.type_lookup(tname)
            if itid < 0:
                continue
            slots = sorted(compiled.slot_of_name[p] for p in d.permissions)
            if slots:
                tbl[itid, slots] = True
                k2p1 = np.asarray(
                    [self.k2d[s] + 1 for s in slots
                     if self.k2d[s] >= 0
                     and self.chain_ok[itid, self.k2d[s] + 1]],
                    np.int64,
                )
                if k2p1.size:
                    self.perm_k2p1_of_tid[itid] = k2p1
        self.perm_raw_table = tbl
        self.ts_slots = sorted(compiled.tupleset_slots)
        arrs = dsnap.arrays
        dummy = jnp.zeros(1, jnp.int32)

        def args_of(off_key):
            return (arrs[off_key], arrs.get(off_key + "_a", dummy))

        self.rv_args = args_of("rv_off") + (arrs["rvx"],)
        self.ra_args = args_of("ra_off") + (arrs["rax"],)
        self.fw_args = (
            args_of("fw_off") + (arrs["fwx"],) if meta.has_fw else None
        )
        al = {k for k, _w, _c in meta.aligned}
        if "argx" in al:
            from .flat import _al_key

            n_lv = len(dict((k, c) for k, _w, c in meta.aligned)["argx"])
            self.arg_args = tuple(arrs[_al_key("argx", l)] for l in range(n_lv))
            self.arg_aligned = True
        else:
            self.arg_args = args_of("arr_off") + (arrs["argx"],)
            self.arg_aligned = False
        self.arx = arrs["arx"]
        #: owner-routed hop backend for bucket-sharded stacked tables
        #: (parallel/sharded.py): each hop's frontier keys route to
        #: their owner shards, only owner-crossing IDs move
        self._hops = (
            engine.lookup_hops_for(dsnap, self.kern)
            if meta.sharded else None
        )
        #: wildcard-widening cache: sorted unique direct subjects
        self._all_subj: Optional[np.ndarray] = None
        #: fused K-hop SpMM server (engine/spmm.py): the whole frontier
        #: fixpoint in ONE pinned dispatch when eligible; None keeps the
        #: looped per-hop path below byte-for-byte (EngineConfig.spmm
        #: off, sharded snapshots, or oversized key domains)
        from . import spmm as _spmm_mod

        self._spmm = _spmm_mod.fused_for(engine, self)

    # -- expansion primitives --------------------------------------------
    def _now(self, now_us):
        import jax.numpy as jnp

        return jnp.int32(self.snap.now_rel32(now_us))

    def expand_rv(self, keys: np.ndarray, now):
        if self._hops is not None:
            return self._hops.expand("rv", keys, now)
        return self.kern.expand("rv", self.rv_args, self.rv_args[2],
                                keys, now)

    def expand_ra(self, keys: np.ndarray, now):
        if self._hops is not None:
            return self._hops.expand("ra", keys, now)
        return self.kern.expand("ra", self.ra_args, self.ra_args[2],
                                keys, now)

    def expand_fw(self, keys: np.ndarray, now):
        if self._hops is not None:
            return self._hops.expand("fw", keys, now)
        return self.kern.expand("fw", self.fw_args, self.fw_args[2],
                                keys, now)

    def expand_arrows_fwd(self, keys: np.ndarray, now):
        """Forward tupleset traversal over the EXISTING argx/arx view."""
        if keys.shape[0] == 0:
            return iter(())
        if self._hops is not None:
            return self._hops.expand("arg", keys, now)
        lo, ln, total = self.kern.runs("arg", self.arg_args, keys)
        _mt.inc("lookup.hops")
        _trace.count_if_active("hops")
        sp = _trace.current()

        def gen():
            at = 0
            while at < total:
                rows, live = self.kern.emit("arg", self.arx, lo, ln, at, now)
                with _trace.stage("lookup.expand", sp):
                    rows = rows[live]
                yield rows
                at += self.kern.CH

        return gen()

    def node_type_of(self, nodes: np.ndarray) -> np.ndarray:
        nt = self.snap.node_type
        out = np.full(nodes.shape[0], -1, np.int64)
        ok = (nodes >= 0) & (nodes < nt.shape[0])
        out[ok] = nt[nodes[ok]]
        return out

    def all_subjects(self) -> np.ndarray:
        if self._all_subj is None:
            self._all_subj = np.unique(self.snap.e_subj).astype(np.int64)
        return self._all_subj

    # -- LookupResources candidate stream --------------------------------
    def resource_candidates(
        self, rtid: int, subj_node: int, srel_slot: int, wc_node: int,
        now_us: Optional[int],
    ) -> Iterator[np.ndarray]:
        """Deterministic stream of candidate resource-node blocks — the
        walker's reverse worklist, each hop one masked SpMV over the
        reverse tables.  Soundness: every DEFINITE grant has a live,
        resolvable positive edge path; the in-kernel gate filter drops
        only edges that can never be part of one.

        With the fused SpMM core (engine/spmm.py) the whole fixpoint
        runs in ONE device dispatch; overflow (frontier/emission/
        candidate capacity, round budget) falls back to the looped
        per-hop body below, which is also the streaming path big
        answers want.

        Stages (utils/trace.py): the host work between dispatches is
        ``lookup.expand``, never open across a ``yield`` or a hop's own
        dispatch stages."""
        sp = _trace.current()
        if self._spmm is not None:
            blocks = self._spmm.resources(
                rtid, subj_node, srel_slot, wc_node, now_us
            )
            if blocks is not None:
                sp.set_attr("path", "fused")
                sp.set_attr("fallback", False)
                for b in blocks:
                    if b.size:
                        _mt.inc("lookup.candidates", b.size)
                        yield b
                return
            _mt.inc("spmm.fallbacks")
            sp.set_attr("fallback", True)
        sp.set_attr("path", "looped")
        N, S1, logN = self.N, self.S1, self.logN
        with _trace.stage("lookup.args", sp):
            now = self._now(now_us)
        with _trace.stage("lookup.expand", sp):
            seen_keys = _Seen(N * S1)
            seen_nodes = _Seen(N)
            nt_shape = self.snap.node_type.shape[0]
            seeds: List[np.ndarray] = []
            if 0 <= subj_node < N:
                if srel_slot < 0:
                    seeds.append(np.asarray([subj_node * S1], np.int64))
                elif self.k2d[srel_slot] >= 0:
                    seeds.append(np.asarray(
                        [subj_node * S1 + int(self.k2d[srel_slot]) + 1],
                        np.int64,
                    ))
            if 0 <= wc_node < N:
                seeds.append(np.asarray([wc_node * S1], np.int64))
            # self-identity: the subject node itself may be the resource
            first_nodes = seen_nodes.fresh(
                np.asarray([subj_node], np.int64)
                if 0 <= subj_node < nt_shape else np.empty(0, np.int64)
            )
            cand = first_nodes[self.node_type_of(first_nodes) == rtid]
            frontier = seen_keys.fresh(
                np.concatenate(seeds) if seeds else np.empty(0, np.int64)
            )
        if cand.size:
            _mt.inc("lookup.candidates", cand.size)
            yield cand
        while frontier.size:
            new_keys: List[np.ndarray] = []
            node_parts: List[np.ndarray] = []
            for rows in self.expand_rv(frontier.astype(np.int32), now):
                if rows.shape[0] == 0:
                    continue
                with _trace.stage("lookup.expand", sp):
                    k1 = rows[:, 1].astype(np.int64)
                    res = k1 & (N - 1)
                    slotd = k1 >> logN
                    node_parts.append(res)
                    # granted usersets continue the membership chain —
                    # only where the schema declares (type(res), rel) a
                    # legal subject form (type-safety pruning: everything
                    # else is structurally dead and never probes)
                    nk = self.k2p1_of_k1d[slotd]
                    chain = (nk > 0) & self.chain_ok[
                        self.node_type_of(res), np.maximum(nk, 0)
                    ]
                    if chain.any():
                        new_keys.append(res[chain] * S1 + nk[chain])
            with _trace.stage("lookup.expand", sp):
                nodes = seen_nodes.fresh(
                    np.concatenate(node_parts)
                    if node_parts else np.empty(0, np.int64)
                )
            # close candidates under reverse arrows (parents granting
            # through tupleset traversal) — device hops over rax
            while nodes.size:
                with _trace.stage("lookup.expand", sp):
                    cand = nodes[self.node_type_of(nodes) == rtid]
                if cand.size:
                    _mt.inc("lookup.candidates", cand.size)
                    yield cand
                with _trace.stage("lookup.expand", sp):
                    if self.perm_chains:
                        tids = self.node_type_of(nodes)
                        for t in np.unique(tids):
                            k2p1 = self.perm_k2p1_of_tid.get(int(t))
                            if k2p1 is None:
                                continue
                            nn = nodes[tids == t]
                            new_keys.append(
                                (nn[:, None] * S1 + k2p1[None, :]).ravel()
                            )
                    # only declared arrow-child types can have parents
                    ch = nodes[self.child_ok[self.node_type_of(nodes)]]
                parent_parts = [
                    rows[:, 1].astype(np.int64) & (N - 1)
                    for rows in self.expand_ra(ch.astype(np.int32), now)
                    if rows.shape[0]
                ]
                with _trace.stage("lookup.expand", sp):
                    nodes = seen_nodes.fresh(
                        np.concatenate(parent_parts)
                        if parent_parts else np.empty(0, np.int64)
                    )
            with _trace.stage("lookup.expand", sp):
                frontier = seen_keys.fresh(
                    np.concatenate(new_keys)
                    if new_keys else np.empty(0, np.int64)
                )

    # -- LookupSubjects candidate stream ---------------------------------
    def subject_candidates(
        self, res_node: int, stid: int, srel_slot: int, wc_node: int,
        now_us: Optional[int],
    ) -> Iterator[np.ndarray]:
        """Forward frontier expansion from the resource over the fw/argx
        views — the walker's node/pair worklist as device hops (or ONE
        fused SpMM dispatch, overflow falling back here).  Stages as in
        ``resource_candidates``."""
        sp = _trace.current()
        if self._spmm is not None:
            blocks = self._spmm.subjects(
                res_node, stid, srel_slot, wc_node, now_us
            )
            if blocks is not None:
                sp.set_attr("path", "fused")
                sp.set_attr("fallback", False)
                for b in blocks:
                    if b.size:
                        _mt.inc("lookup.candidates", b.size)
                        yield b
                return
            _mt.inc("spmm.fallbacks")
            sp.set_attr("fallback", True)
        sp.set_attr("path", "looped")
        N, S1, logN = self.N, self.S1, self.logN
        snap = self.snap
        num_slots = max(snap.num_slots, 1)
        with _trace.stage("lookup.args", sp):
            now = self._now(now_us)
        with _trace.stage("lookup.expand", sp):
            seen_nodes = _Seen(N)
            seen_pairs = _Seen(N * (num_slots + 1))
            seen_cand = _Seen(N)
            pair_list: List[np.ndarray] = []  # raw (g·NS + r) pairs, for srel
            wildcard_found = [False]
            # dense k2 value+1 → raw slot (decoding emitted userset subjects)
            k2p1_raw = np.full(S1 + 1, -1, np.int64)
            for raw, d in enumerate(self.k2d):
                if d >= 0:
                    k2p1_raw[d + 1] = raw
            e_slot_raw = np.asarray(
                [s for s in self.meta.e_slots if self.k1d[s] >= 0], np.int64
            )
            e_slot_k1d = self.k1d[e_slot_raw].astype(np.int64)
            ts_raw = np.asarray(
                [s for s in self.ts_slots if self.k1d[s] >= 0], np.int64
            )
            ts_k1d = self.k1d[ts_raw].astype(np.int64)
            node_frontier = seen_nodes.fresh(
                np.asarray([res_node], np.int64)
                if 0 <= res_node < N else np.empty(0, np.int64)
            )

        def absorb(k2vals: np.ndarray):
            """Emitted subject keys → (direct candidate block or None,
            new raw pairs)."""
            direct = k2vals % S1 == 0
            dn = k2vals[direct] // S1
            cand = None
            if srel_slot < 0 and dn.size:
                fresh = seen_cand.fresh(dn[self.node_type_of(dn) == stid])
                cand = fresh if fresh.size else None
            if (
                wc_node >= 0 and not wildcard_found[0]
                and dn.size and bool(np.any(dn == wc_node))
            ):
                wildcard_found[0] = True
            um = ~direct
            g = k2vals[um] // S1
            r = k2p1_raw[k2vals[um] % S1]
            pairs = g * (num_slots + 1) + r  # r ≥ 0: emitted userset rows
            return cand, pairs

        def fw_keys_of_nodes(nodes: np.ndarray) -> np.ndarray:
            if nodes.size == 0 or e_slot_k1d.size == 0:
                return np.empty(0, np.int64)
            # type-safety pruning: only (slot, node) pairs where the
            # node's type declares the relation can have edges
            ok = self.slot_of_type[
                self.node_type_of(nodes)[:, None], e_slot_raw[None, :]
            ]
            kk = nodes[:, None] + (e_slot_k1d[None, :] * N)
            return kk[ok].ravel()

        def fw_hop(keys: np.ndarray, new_pairs: List[np.ndarray]):
            """One hop over the fw view: yields each emitted chunk's
            fresh direct candidates, appends its userset pairs."""
            for rows in self.expand_fw(keys.astype(np.int32), now):
                if rows.shape[0] == 0:
                    continue
                with _trace.stage("lookup.expand", sp):
                    cand, pairs = absorb(rows[:, 1].astype(np.int64))
                    if pairs.size:
                        new_pairs.append(pairs)
                if cand is not None:
                    _mt.inc("lookup.candidates", cand.size)
                    yield cand

        pair_frontier = np.empty(0, np.int64)
        pending_nodes: List[np.ndarray] = []
        while node_frontier.size or pair_frontier.size:
            new_pairs: List[np.ndarray] = []
            if node_frontier.size:
                # arrow closure of the frontier, then every edge off it
                fresh_all: List[np.ndarray] = [node_frontier]
                cur = node_frontier
                while cur.size and ts_k1d.size:
                    with _trace.stage("lookup.expand", sp):
                        tok = self.slot_of_type[
                            self.node_type_of(cur)[:, None], ts_raw[None, :]
                        ]
                        keys = (cur[:, None] + ts_k1d[None, :] * N)[tok].ravel()
                    child_parts = [
                        rows[:, 0].astype(np.int64)
                        for rows in self.expand_arrows_fwd(
                            keys.astype(np.int32), now
                        )
                        if rows.shape[0]
                    ]
                    with _trace.stage("lookup.expand", sp):
                        cur = seen_nodes.fresh(
                            np.concatenate(child_parts)
                            if child_parts else np.empty(0, np.int64)
                        )
                    if cur.size:
                        fresh_all.append(cur)
                with _trace.stage("lookup.expand", sp):
                    keys = fw_keys_of_nodes(np.concatenate(fresh_all))
                yield from fw_hop(keys, new_pairs)
            if pair_frontier.size:
                with _trace.stage("lookup.expand", sp):
                    g = pair_frontier // (num_slots + 1)
                    r = pair_frontier % (num_slots + 1)
                    tids = self.node_type_of(g)
                    ok_t = (tids >= 0) & (r < num_slots)
                    is_perm = np.zeros(g.shape[0], bool)
                    if self.perm_raw_table is not None:
                        is_perm[ok_t] = self.perm_raw_table[
                            tids[ok_t], r[ok_t]
                        ]
                    # permission pairs: holders of g#p ⊆ expansion of g
                    pending_nodes.append(g[is_perm])
                    rel_g, rel_r = g[~is_perm], r[~is_perm]
                    kd = self.k1d[np.clip(rel_r, 0, self.k1d.shape[0] - 1)]
                    okk = (kd >= 0) & (rel_r < self.k1d.shape[0])
                    keys = kd[okk] * N + rel_g[okk]
                yield from fw_hop(keys, new_pairs)
            with _trace.stage("lookup.expand", sp):
                pair_frontier = seen_pairs.fresh(
                    np.concatenate(new_pairs)
                    if new_pairs else np.empty(0, np.int64)
                )
                if pair_frontier.size:
                    pair_list.append(pair_frontier)
                node_frontier = seen_nodes.fresh(
                    np.concatenate(pending_nodes)
                    if pending_nodes else np.empty(0, np.int64)
                )
                pending_nodes = []

        # trailing blocks, same order as the walker's tail
        if srel_slot >= 0 and pair_list:
            with _trace.stage("lookup.expand", sp):
                allp = np.concatenate(pair_list)
                gs = allp[allp % (num_slots + 1) == srel_slot] // (num_slots + 1)
                cand = seen_cand.fresh(gs[self.node_type_of(gs) == stid])
            if cand.size:
                _mt.inc("lookup.candidates", cand.size)
                yield cand
        if 0 <= res_node and self.node_type_of(
            np.asarray([res_node], np.int64)
        )[0] == stid:
            cand = seen_cand.fresh(np.asarray([res_node], np.int64))
            if cand.size:
                yield cand
        if wildcard_found[0] and srel_slot < 0:
            with _trace.stage("lookup.expand", sp):
                subs = self.all_subjects()
                cand = seen_cand.fresh(subs[self.node_type_of(subs) == stid])
            if cand.size:
                _mt.inc("lookup.candidates", cand.size)
                yield cand


def state_for(engine, dsnap) -> FrontierState:
    st = dsnap.__dict__.get("_frontier_state")
    if st is None or st.engine is not engine:
        st = FrontierState(engine, dsnap)
        dsnap.__dict__["_frontier_state"] = st
    return st


# ---------------------------------------------------------------------------
# cursor-paginated result streaming (shared by frontier + walker paths)
# ---------------------------------------------------------------------------


class _ResultStream:
    """A lookup's granted-result stream: candidate blocks → exact filter
    → result ids, with the emitted-count bookkeeping cursors resume on.
    A granted block becomes its id strings in ONE ``ids_of`` call (the
    interner's columnar decode), in the block's order — never a call an
    id: four callers turn a per-id foreign call into a hand-over of the
    interpreter lock an id."""

    def __init__(self, cand_iter: Iterator[np.ndarray],
                 filter_fn: Callable[[np.ndarray], np.ndarray],
                 ids_of: Callable[[np.ndarray], List[str]],
                 cost_bytes: int = 1 << 20) -> None:
        self._cands = cand_iter
        self._filter = filter_fn
        self._ids_of = ids_of
        self._pending: List[str] = []
        self.emitted = 0
        self.exhausted = False
        #: estimated held host bytes (frontier seen-set bitmaps dominate)
        #: — paginate's cache evicts by this, not just count
        self.cost_bytes = int(cost_bytes)

    def take(self, n: int) -> List[str]:
        sp = _trace.current()
        out: List[str] = []
        while len(out) < n:
            if self._pending:
                k = min(n - len(out), len(self._pending))
                out.extend(self._pending[:k])
                del self._pending[:k]
                continue
            # the candidate generator and the filter run their own
            # stages: none is open across either call
            block = next(self._cands, None)
            if block is None:
                self.exhausted = True
                break
            if block.size == 0:
                continue
            _trace.count_if_active("blocks")
            _trace.count_if_active("candidates", int(block.size))
            granted = self._filter(block)
            if granted.size == 0:
                continue
            with _trace.stage("lookup.decode", sp):
                self._pending.extend(self._ids_of(granted))
            _mt.inc("lookup.id_blocks")
            _mt.inc("lookup.ids", int(granted.size))
        self.emitted += len(out)
        return out

    def skip(self, n: int) -> None:
        while n > 0:
            got = self.take(min(n, 4096))
            n -= len(got)
            if self.exhausted and not self._pending and not got:
                break


#: byte budget for cached live continuations per DeviceSnapshot: a big
#: world's stream holds seen-set bitmaps (up to _SEEN_BUDGET_BYTES
#: each), so eviction is by ESTIMATED bytes, with the count cap as the
#: small-stream backstop
_STREAM_CACHE_BYTES = 256 << 20


def open_stream(
    dsnap,
    token: str,
    make_stream: Callable[[], _ResultStream],
    cursor: Optional[LookupCursor],
) -> Tuple[_ResultStream, int]:
    """The live stream a page reads, and how many results it must skip
    first: the one cached on the DeviceSnapshot under ``token`` where it
    stands at the cursor, else a fresh ``make_stream()`` that skips
    ``cursor.pos`` (an evicted or cross-process resume recomputes
    deterministically)."""
    from ..utils.errors import PreconditionFailedError

    pos = 0
    if cursor is not None:
        if cursor.token != token:
            raise PreconditionFailedError(
                "lookup cursor does not match this query"
            )
        if cursor.revision != dsnap.revision:
            raise PreconditionFailedError(
                f"lookup cursor pinned to revision {cursor.revision}, "
                f"snapshot is at {dsnap.revision}"
            )
        pos = cursor.pos
    stream = dsnap.__dict__.setdefault("_lookup_streams", {}).pop(token, None)
    if stream is not None and stream.emitted == pos:
        return stream, 0
    _mt.inc("lookup.stream_recomputes" if pos else "lookup.streams")
    return make_stream(), pos


def paginate(
    dsnap,
    token: str,
    stream: _ResultStream,
    skip: int,
    page_size: int,
    now_us: Optional[int] = None,
) -> Tuple[List[str], Optional[LookupCursor]]:
    """One page of results with exact resume semantics, from the stream
    and skip ``open_stream`` gave.  An unfinished stream is cached on the
    DeviceSnapshot keyed by ``token``.  ``now_us`` (already resolved via
    resolve_now_us) rides the returned cursor so a recompute is evaluated
    at the same instant."""
    cache: Dict[str, _ResultStream] = dsnap.__dict__.setdefault(
        "_lookup_streams", {}
    )
    stream.skip(skip)
    ids = stream.take(page_size)
    done = stream.exhausted and not stream._pending
    nxt = None
    if not done:
        nxt = LookupCursor(dsnap.revision, token, stream.emitted, now_us)
        cache[token] = stream
        while len(cache) > _STREAM_CACHE_MAX or (
            len(cache) > 1
            and sum(s.cost_bytes for s in cache.values())
            > _STREAM_CACHE_BYTES
        ):
            cache.pop(next(iter(cache)))
    return ids, nxt
