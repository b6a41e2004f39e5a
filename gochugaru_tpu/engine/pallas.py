"""Pallas fused probe backend: one HBM pass per probed table.

The XLA check kernel (engine/flat.py) compiles each bucket probe as a
chain of separate gather ops — bucket-offset read, contiguous block gather,
packed shift/mask decode, key compare, caveat/expiry gate, OR reduce —
and XLA materializes the gathered (and then the decoded) block between
the stages it cannot fuse across a gather.  On TPU those intermediates
cross HBM; the roofline ledger (utils/perf.py) says the superseded
kernel reached 2-3% of the measured ceiling, and the remaining bytes
are exactly these re-crossings.

This module hand-fuses the whole probe into ONE Pallas kernel per site:

    hash (mix32) → bucket offset (anchor + residual, VMEM-resident)
      → bucket block fetch (double-buffered async-copy DMA from HBM)
      → packed ``decode_block`` in registers
      → key compare (+ expiry/until gate where the site allows)
      → short-circuited OR reduce

so the packed table bytes cross HBM exactly once and the kernel's
output is the site's REDUCED answer (or the few gate lanes the CEL tri
VM still needs), never the decoded block.  Hot state — bucket offsets,
offset anchors, aligned-ladder rows under ``VMEM_TABLE_MAX_BYTES`` —
rides VMEM for the whole batch instead of being re-gathered from HBM
per probe (``perf.vmem_resident_bytes`` reports what is pinned).

Kernel modes (one builder, static tails):

- ``block``   decoded int32[B, cap, W] candidate block — the drop-in
              ``pblock`` replacement; parity with the XLA path is
              bitwise by construction (same clamp, same rows, same
              decode).
- ``any``     bool[B] hit-any (pus / closure-overflow sites): compare
              AND reduce fused, no block output at all.
- ``until2``  (bool[B], bool[B]) — hit ∧ until-plane > now for lanes
              2/3 (T-index and closure probes), reduced in-kernel.
- ``gate``    (hit, live[, cav, ctx]) [B, cap] lanes — the direct-edge
              probe: expiry gate fused; the CEL tri VM (caveats/
              device.py) consumes the cav/ctx lanes outside, which are
              ~W/4 of the decoded block the XLA path materializes.
- ``runs``    (lo, ln) int32[B] — the frontier/SpMM run probe
              (engine/spmv.py): offset + in-bucket bisect over the
              DMA'd block, so the K-hop lookup programs inherit the
              fused probe too.

Selection (``resolve``): ``EngineConfig.pallas`` None resolves OFF on
every platform — the XLA chain in engine/flat.py is the main path.
Mosaic refuses every kernel mode here on a v5e (the refusals are quoted
in CHANGES.md, PR 21): the kernels read and write ``ANY``-space refs
with plain indexing, their DMA windows are far below the (8,128) tile,
and their outputs are written one scalar at a time.  Rewriting them for
Mosaic (scalar-prefetch grid, tile-shaped windows) is a performance
change of its own.  ``pallas=True`` is a demand, never a wish: on a TPU
the kernels compile with Mosaic or the dispatch raises; on the CPU
backend — and only there — they run through the Pallas interpreter,
which checks CORRECTNESS (bitwise parity against the XLA path on
randomized worlds), not speed.  The one-pass byte accounting is a model
(utils/perf.py ``pallas_bytes_model``), asserted structurally in tests;
its device effect is not measured.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from ..utils import metrics as _metrics

_mt = _metrics.default


def resolve(config) -> bool:
    """The resolved ``EngineConfig.pallas`` flag: on only when the
    caller demands it (True).  None — the default — is off on every
    platform, because Mosaic does not lower these kernels (module
    docstring); a forced True on a TPU compiles for real or raises."""
    return getattr(config, "pallas", None) is True


def interpret_mode() -> bool:
    """The Pallas interpreter runs the kernels on the CPU backend and
    nowhere else: any other backend lowers them with its own compiler
    or fails."""
    import jax

    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# VMEM residency plan
# ---------------------------------------------------------------------------

#: per-array ceiling for pinning an offsets/anchor/ladder array
#: VMEM-resident.  VMEM is ~16 MB/core and Mosaic's default scoped limit
#: is 16 MiB for the whole kernel, so several arrays at this ceiling do
#: not fit together — a kernel rewrite must budget the sum, not each
VMEM_TABLE_MAX_BYTES = 4 << 20


def _nbytes(a) -> int:
    return int(np.prod(a.shape)) * int(np.dtype(a.dtype).itemsize)


def require_vmem(name: str, *arrays) -> None:
    """The fused kernels pin a site's offset arrays VMEM-resident; one
    over the ceiling cannot be served.  ``pallas=True`` is a demand, so
    that is an error at trace time — never a quiet return to the XLA
    chain."""
    for a in arrays:
        if a is not None and _nbytes(a) > VMEM_TABLE_MAX_BYTES:
            raise ValueError(
                f"pallas=True: `{name}` is {_nbytes(a)} bytes, over the"
                f" {VMEM_TABLE_MAX_BYTES}-byte VMEM-resident ceiling of"
                " the fused probe kernel; this table needs the XLA chain"
                " (pallas=None/False)"
            )


def vmem_plan(arrays) -> Dict[str, int]:
    """{key: nbytes} of the arrays the fused kernels pin VMEM-resident:
    bucket offsets, packed-offset anchors, and aligned-ladder level
    tables under the per-array budget.  Pure shape arithmetic — safe at
    prepare time on host or device arrays."""
    out: Dict[str, int] = {}
    for k, v in arrays.items():
        if not (
            k.endswith("_off") or k.endswith("_off_a")
            or k.endswith("_start") or "_al" in k
        ):
            continue
        nb = _nbytes(v)
        if nb <= VMEM_TABLE_MAX_BYTES:
            out[k] = nb
    return out


def publish_vmem(arrays, registry: Optional[_metrics.Metrics] = None) -> int:
    """Publish ``perf.vmem_resident_bytes`` (the hot state the fused
    kernels keep on-chip for the whole batch) at prepare time."""
    m = registry or _metrics.default
    total = sum(vmem_plan(arrays).values())
    m.set_gauge("perf.vmem_resident_bytes", float(total))
    return total


# ---------------------------------------------------------------------------
# the fused probe kernel
# ---------------------------------------------------------------------------

_FNV_OFFSET = 2166136261
_FNV_PRIME = 16777619


def _mix32_scalar(vals, jnp):
    """mix32 (engine/hash.py) on in-kernel scalars — identical uint32
    wrap-around arithmetic, so the bucket choice is bit-identical."""
    h = jnp.uint32(_FNV_OFFSET)
    for v in vals:
        h = (h ^ v.astype(jnp.uint32)) * jnp.uint32(_FNV_PRIME)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _decode(blk, spec, jnp):
    """packed.decode_block, restated kernel-safe.

    The stock decode materializes dictionary columns with
    ``jnp.asarray(dicts[id])[v]`` — a gather from a *captured constant
    array*, which ``pallas_call`` rejects (kernel closures may not hold
    array constants).  The dict values are static Python ints, so inside
    the kernel the lookup becomes a select chain over the (tiny, ≤256)
    domain — bitwise-equal to the gather for every in-domain index, and
    pack_rows guarantees all stored indices are in-domain."""
    if spec is None:
        return blk.astype(jnp.int32)
    w, lanes, fields, dicts = spec
    l32 = blk.astype(jnp.int32)
    cols = [None] * w
    for j, (bits, base, delta_of, dict_id, off_bit) in enumerate(fields):
        if bits == 0:
            col = jnp.full(blk.shape[:-1], base, jnp.int32)
        else:
            lane, sh = off_bit >> 4, off_bit & 15
            v = l32[..., lane] >> sh if sh else l32[..., lane]
            if sh + bits > 16:
                v = v | (l32[..., lane + 1] << (16 - sh))
            if bits < 32:
                v = v & jnp.int32((1 << bits) - 1)
            if dict_id >= 0:
                dv = dicts[dict_id]
                col = jnp.full(v.shape, dv[0], jnp.int32)
                for i, val in enumerate(dv[1:], 1):
                    col = jnp.where(v == i, jnp.int32(val), col)
            else:
                col = v + jnp.int32(base) if base else v
        if delta_of >= 0:
            col = col + cols[delta_of]
        cols[j] = col
    return jnp.stack(cols, axis=-1)


def fused_probe(
    q_cols: Sequence,
    off,
    tbl,
    *,
    cap: int,
    spec=None,
    off_a=None,
    ashift: Optional[int] = None,
    mode: str = "block",
    now=None,
    gate: Tuple[bool, bool, bool] = (False, False, False),
    lay: Optional[Dict[str, int]] = None,
):
    """One fused bucket probe over the off+interleave layout.

    ``q_cols`` are the query key columns (any lattice shape, flattened
    here and restored on return); ``off``/``off_a`` the bucket offsets
    (+ packed anchor, shift ``ashift``); ``tbl`` the interleaved block
    table; ``spec`` the packed decode spec (None = plain int32 table).
    ``mode``/``gate``/``lay``/``now`` select the fused tail — see the
    module docstring.  Returns mode-shaped arrays.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interp = interpret_mode()
    shape = np.broadcast_shapes(*[tuple(c.shape) for c in q_cols])
    qf = [
        jnp.broadcast_to(c, shape).reshape(-1).astype(jnp.int32)
        for c in q_cols
    ]
    B = int(qf[0].shape[0])
    NQ = len(qf)
    rows, w_raw = int(tbl.shape[0]), int(tbl.shape[1])
    W = int(spec[0]) if spec is not None else w_raw
    size = int(off.shape[0]) - 1
    packed_off = off_a is not None
    hasexp, hascav, needctx = gate
    _mt.inc("pallas.kernel_traces")

    def _start_of(i, refs):
        """Scalar bucket start of query ``i`` (hash → offset read) —
        recomputed at wait time, so the DMA pipeline carries nothing."""
        qs = [refs[j][i] for j in range(NQ)]
        h = (
            _mix32_scalar(qs, jnp) & jnp.uint32(size - 1)
        ).astype(jnp.int32)
        if packed_off:
            o_ref, a_ref = refs[NQ], refs[NQ + 1]
            start = a_ref[h >> ashift] + o_ref[h].astype(jnp.int32)
        else:
            start = refs[NQ][h]
        # slice_blocks' clamp, verbatim: 0 ≤ s ≤ rows - cap
        return jnp.clip(start, 0, rows - cap), qs

    n_in = NQ + (2 if packed_off else 1) + (1 if now is not None else 0)

    def kern(*refs):
        ins = refs[:n_in]
        tbl_ref = refs[n_in]
        outs = refs[n_in + 1:-2]
        scratch, sem = refs[-2], refs[-1]
        nr = ins[-1][0] if now is not None else None

        def fetch(i, slot):
            s0, _ = _start_of(i, ins)
            return pltpu.make_async_copy(
                tbl_ref.at[pl.ds(s0, cap)], scratch.at[slot], sem.at[slot]
            )

        fetch(0, 0).start()

        def body(i, _):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < B)
            def _():  # software pipeline: next bucket in flight
                fetch(i + 1, nxt).start()

            fetch(i, slot).wait()
            _s0, qs = _start_of(i, ins)
            blk = _decode(scratch[slot], spec, jnp)  # [cap, W] registers
            if mode == "runs":
                _emit_runs(i, qs, blk, _s0, outs)
                return 0
            hit = jnp.ones((cap,), bool)
            guard = None
            for j, q in enumerate(qs):
                hit = hit & (blk[:, j] == q)
                guard = (q >= 0) if guard is None else (guard & (q >= 0))
            hit = hit & guard
            if mode == "block":
                outs[0][i] = blk
            elif mode == "any":
                outs[0][i] = jnp.any(hit)
            elif mode == "until2":
                outs[0][i] = jnp.any(hit & (blk[:, 2] > nr))
                outs[1][i] = jnp.any(hit & (blk[:, 3] > nr))
            else:  # gate
                live = hit
                if hasexp:
                    exp = jnp.where(hit, blk[:, lay["exp"]], 0)
                    live = hit & ((exp == 0) | (exp > nr))
                outs[0][i] = hit
                outs[1][i] = live
                if hascav and needctx:
                    outs[2][i] = jnp.where(hit, blk[:, lay["cav"]], 0)
                    outs[3][i] = jnp.where(hit, blk[:, lay["ctx"]], -1)
                elif hascav:
                    outs[2][i] = jnp.where(hit, blk[:, lay["cav"]], 0)
            return 0

        jax.lax.fori_loop(0, B, body, 0)

    def _emit_runs(i, qs, blk, s0, outs):
        """In-bucket bisect over the DMA'd block — spmv._make_runs'
        math verbatim, reading col0 from the VMEM copy."""
        o_ref = refs_runs["o"]
        h = (
            _mix32_scalar(qs, jnp) & jnp.uint32(size - 1)
        ).astype(jnp.int32)
        if packed_off:
            a_ref = refs_runs["a"]
            start = a_ref[h >> ashift] + o_ref[h].astype(jnp.int32)
            end = a_ref[(h + 1) >> ashift] + o_ref[h + 1].astype(jnp.int32)
        else:
            start = o_ref[h]
            end = o_ref[h + 1]
        last = rows - 1
        col0 = blk[:, 0]
        steps = max(int(cap).bit_length(), 1)
        key = qs[0]

        def bisect(left: bool):
            lo = start
            n = end - start
            for _ in range(steps):
                alive = n > 0
                half = n >> 1
                mid = lo + half
                v = col0[jnp.clip(mid, 0, last) - s0]
                go = alive & ((v < key) if left else (v <= key))
                lo = jnp.where(go, mid + 1, lo)
                n = jnp.where(go, n - half - 1, jnp.where(alive, half, 0))
            return lo

        lo = bisect(True)
        ln = bisect(False) - lo
        dead = key < 0
        outs[0][i] = jnp.where(dead, 0, lo)
        outs[1][i] = jnp.where(dead, 0, ln)

    refs_runs: Dict[str, Any] = {}

    # ---- specs: queries + offsets VMEM-resident, table stays in HBM ----
    vm = pl.ANY
    in_specs = [pl.BlockSpec(memory_space=vm) for _ in range(n_in + 1)]
    out_specs, out_shapes = _out_layout(mode, B, cap, W, gate, jnp, pl, vm)
    args = list(qf)
    args.append(off)
    if packed_off:
        args.append(off_a)
    if now is not None:
        args.append(jnp.reshape(now, (1,)).astype(jnp.int32))
    args.append(tbl)

    if mode == "runs":
        # the bisect tail reads the offset refs directly; expose them
        # through the closure by index (qf..., off[, off_a][, now], tbl)
        def kern_runs(*refs):
            refs_runs["o"] = refs[NQ]
            if packed_off:
                refs_runs["a"] = refs[NQ + 1]
            kern(*refs)

        body_fn = kern_runs
    else:
        body_fn = kern

    outs = pl.pallas_call(
        body_fn,
        out_shape=out_shapes,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, cap, w_raw), tbl.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interp,
    )(*args)

    return _reshape_out(mode, outs, shape, gate)


def fused_probe_aligned(
    q_cols: Sequence,
    tbls: Sequence,
    caps: Sequence[int],
    sw: int,
    *,
    spec=None,
    mode: str = "block",
    now=None,
    gate: Tuple[bool, bool, bool] = (False, False, False),
    lay: Optional[Dict[str, int]] = None,
):
    """The aligned-ladder twin of :func:`fused_probe`: one row DMA per
    width-stratum level (level ≥ 1 salted — hash.probe_aligned's math
    verbatim), levels concatenated and decoded in registers.  Small
    ladder levels sit VMEM-resident; the fused tail is shared."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .hash import _level_salt

    interp = interpret_mode()
    shape = np.broadcast_shapes(*[tuple(c.shape) for c in q_cols])
    qf = [
        jnp.broadcast_to(c, shape).reshape(-1).astype(jnp.int32)
        for c in q_cols
    ]
    B = int(qf[0].shape[0])
    NQ = len(qf)
    L = len(tbls)
    capT = int(sum(caps))
    W = int(spec[0]) if spec is not None else sw
    sizes = [int(t.shape[0]) for t in tbls]
    hasexp, hascav, needctx = gate
    _mt.inc("pallas.kernel_traces")

    n_in = NQ + (1 if now is not None else 0)

    def kern(*refs):
        ins = refs[:n_in]
        tbl_refs = refs[n_in:n_in + L]
        outs = refs[n_in + L:-2 * L]
        scratches = refs[-2 * L:-L]
        sems = refs[-L:]
        nr = ins[NQ][0] if now is not None else None

        def q_at(i):
            return [ins[j][i] for j in range(NQ)]

        def h_of(qs, lvl):
            q0 = qs[0] ^ jnp.int32(_level_salt(lvl)) if lvl else qs[0]
            return (
                _mix32_scalar([q0] + list(qs[1:]), jnp)
                & jnp.uint32(sizes[lvl] - 1)
            ).astype(jnp.int32)

        def fetch(i, slot, lvl):
            h = h_of(q_at(i), lvl)
            return pltpu.make_async_copy(
                tbl_refs[lvl].at[pl.ds(h, 1)],
                scratches[lvl].at[slot],
                sems[lvl].at[slot],
            )

        for lvl in range(L):
            fetch(0, 0, lvl).start()

        def body(i, _):
            slot = jax.lax.rem(i, 2)
            nxt = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < B)
            def _():
                for lvl in range(L):
                    fetch(i + 1, nxt, lvl).start()

            qs = q_at(i)
            parts = []
            for lvl in range(L):
                fetch(i, slot, lvl).wait()
                parts.append(
                    scratches[lvl][slot].reshape(caps[lvl], sw)
                )
            raw = parts[0] if L == 1 else jnp.concatenate(parts, axis=0)
            blk = _decode(raw, spec, jnp)  # [capT, W]
            hit = jnp.ones((capT,), bool)
            guard = None
            for j, q in enumerate(qs):
                hit = hit & (blk[:, j] == q)
                guard = (q >= 0) if guard is None else (guard & (q >= 0))
            hit = hit & guard
            if mode == "block":
                outs[0][i] = blk
            elif mode == "any":
                outs[0][i] = jnp.any(hit)
            elif mode == "until2":
                outs[0][i] = jnp.any(hit & (blk[:, 2] > nr))
                outs[1][i] = jnp.any(hit & (blk[:, 3] > nr))
            else:  # gate
                live = hit
                if hasexp:
                    exp = jnp.where(hit, blk[:, lay["exp"]], 0)
                    live = hit & ((exp == 0) | (exp > nr))
                outs[0][i] = hit
                outs[1][i] = live
                if hascav and needctx:
                    outs[2][i] = jnp.where(hit, blk[:, lay["cav"]], 0)
                    outs[3][i] = jnp.where(hit, blk[:, lay["ctx"]], -1)
                elif hascav:
                    outs[2][i] = jnp.where(hit, blk[:, lay["cav"]], 0)
            return 0

        jax.lax.fori_loop(0, B, body, 0)

    vm = pl.ANY
    in_specs = [pl.BlockSpec(memory_space=vm) for _ in range(n_in + L)]
    out_specs, out_shapes = _out_layout(
        mode, B, capT, W, gate, jnp, pl, vm
    )
    args = list(qf)
    if now is not None:
        args.append(jnp.reshape(now, (1,)).astype(jnp.int32))
    args.extend(tbls)

    outs = pl.pallas_call(
        kern,
        out_shape=out_shapes,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=(
            [pltpu.VMEM((2, 1, int(t.shape[1])), t.dtype) for t in tbls]
            + [pltpu.SemaphoreType.DMA((2,)) for _ in tbls]
        ),
        interpret=interp,
    )(*args)

    return _reshape_out(mode, outs, shape, gate)


def _out_layout(mode, B, cap, W, gate, jnp, pl, vm):
    """(out_specs, out_shapes) per kernel mode."""
    import jax

    hasexp, hascav, needctx = gate
    if mode == "block":
        shapes = [jax.ShapeDtypeStruct((B, cap, W), jnp.int32)]
    elif mode == "any":
        shapes = [jax.ShapeDtypeStruct((B,), jnp.bool_)]
    elif mode == "until2":
        shapes = [
            jax.ShapeDtypeStruct((B,), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.bool_),
        ]
    elif mode == "runs":
        shapes = [
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ]
    else:  # gate
        shapes = [
            jax.ShapeDtypeStruct((B, cap), jnp.bool_),
            jax.ShapeDtypeStruct((B, cap), jnp.bool_),
        ]
        if hascav:
            shapes.append(jax.ShapeDtypeStruct((B, cap), jnp.int32))
            if needctx:
                shapes.append(jax.ShapeDtypeStruct((B, cap), jnp.int32))
    specs = [pl.BlockSpec(memory_space=vm) for _ in shapes]
    return specs, shapes


def _reshape_out(mode, outs, shape, gate):
    """Restore the caller's query-lattice shape on every output."""
    hasexp, hascav, needctx = gate
    if mode == "block":
        blk = outs if not isinstance(outs, (list, tuple)) else outs[0]
        return blk.reshape(shape + blk.shape[1:])
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    done = [o.reshape(shape + o.shape[1:]) for o in outs]
    if mode == "any":
        return done[0]
    return tuple(done)
