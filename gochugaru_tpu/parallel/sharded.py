"""The sharded bulk-check engine: shard_map over a (data × model) mesh.

Queries are partitioned along ``data`` (each device row evaluates its own
slice of the batch), the sorted edge columns along ``model`` (each device
column holds a contiguous, still-sorted block of every view).  The engine
body is exactly the single-chip two-phase evaluation with collectives at
the merge points (``engine.device`` with ``axis=MODEL_AXIS``):

- closure seed/propagation gathers all-gather shard-local candidates;
- leaf tests OR-reduce shard-local hits (all-reduce over ICI);
- the arrow BFS all-gathers shard-local children, then assigns node slots
  deterministically so every shard holds the identical subgraph.

This is the SPMD replacement for what a multi-node SpiceDB does with its
dispatch cluster (SURVEY.md §2.5): one XLA program, collectives riding
ICI, no RPC fan-out.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..engine.device import (
    DeviceEngine,
    DeviceSnapshot,
    _ceil_pow2,
    _make_check_fn,
    _pad_payload,
    subject_rows,
)
from ..engine.flat import build_qm
from ..engine.plan import EngineConfig
from ..rel.relationship import Relationship
from ..schema.compiler import CompiledSchema
from ..store.snapshot import Snapshot
from ..utils import faults, metrics
from ..utils import trace as _trace
from .mesh import DATA_AXIS, MODEL_AXIS


class ShardedEngine(DeviceEngine):
    """A DeviceEngine whose batched check runs shard_mapped over a mesh."""

    def __init__(
        self,
        compiled: CompiledSchema,
        mesh: Mesh,
        config: Optional[EngineConfig] = None,
    ) -> None:
        super().__init__(compiled, config)
        self.mesh = mesh
        self.data_size = mesh.shape[DATA_AXIS]
        self.model_size = mesh.shape[MODEL_AXIS]
        raw = _make_check_fn(
            self.plan, self.config, axis=MODEL_AXIS, jit=False,
            caveat_plan=self.caveat_plan,
        )

        def arr_spec_of(key: str):
            # lookup tables (node type map, caveat context tables, the
            # static possibly-userset pair set — probed whole by every
            # leaf test) are replicated; sorted edge columns shard along
            # the model axis
            if key == "node_type" or key.startswith(("ectx_", "pus_")):
                return P()
            return P(MODEL_AXIS)

        self._arr_spec_of = arr_spec_of
        arr_spec = {k: arr_spec_of(k) for k in self._array_keys()}
        qctx_spec = {k: P() for k in ("vi", "vf", "pr", "host")}
        in_specs = (
            arr_spec, P(), P(),  # arrays, tid_map, now
            P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),  # u_*
            P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),  # q_res, q_perm, q_subj
            P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),  # srel, wc, row, self
            P(DATA_AXIS),  # q_ctx
            qctx_spec,
        )
        out_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
        self._fn = jax.jit(
            shard_map(
                raw, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )
        #: shard_mapped flat kernels per (slots, FlatMeta, array keys)
        self._flat_sharded_fns: Dict = {}

    def _array_keys(self):
        # single source of truth for the column set lives in DeviceEngine
        # (ARRAY_COLUMN_KEYS), so a new column added to _host_arrays can't
        # silently diverge from the shard_map specs
        keys = list(DeviceEngine.ARRAY_COLUMN_KEYS)
        if self.caveat_plan is not None:
            keys += ["ectx_vi", "ectx_vf", "ectx_pr", "ectx_host"]
        return keys

    # -- flat (bucket-sharded) path ---------------------------------------
    @staticmethod
    def _flat_spec_of(key: str):
        """Sharded flat tables split on the leading (stacked) axis; node
        types, stored-context tables, and the delta-sized ``dl_*``
        overlays are replicated."""
        if key == "node_type" or key.startswith(("ectx_", "dl_")):
            return P()
        return P(MODEL_AXIS)

    @staticmethod
    def _part_spec_of(key: str):
        """Partitioned-serve placement (FlatMeta.part_serve): the
        O(E)-scale point tables (primary, fold, T join) split along the
        model axis; every other stacked table is membership-/group-
        structure-sized and resident whole per device (the kernel
        resolves their bucket owners arithmetically — no collective at
        those sites)."""
        from ..engine.flat import PART_SHARDED_KEYS

        return P(MODEL_AXIS) if key in PART_SHARDED_KEYS else P()

    def _spec_fn_for(self, meta):
        return self._part_spec_of if (
            meta is not None and meta.part_serve
        ) else self._flat_spec_of

    def _flat_sharded_fn(
        self, slots: Tuple[int, ...], meta, arr_keys, routed: bool = False
    ):
        """Cache of shard_mapped flat kernels per (slots, meta, keys,
        routed).  A ROUTED kernel takes the query matrix split along the
        model axis (each shard holds exactly the queries whose root
        bucket it owns) and compiles with no collectives; the plain
        kernel replicates the batch along model and psums the e/pf
        sites (part_serve) or every site (classic stacked layout)."""
        key = (slots, meta, arr_keys, routed)
        fn = self._flat_sharded_fns.get(key)
        if fn is not None:
            return fn
        from ..engine.flat import make_flat_fn

        raw = make_flat_fn(
            self.compiled, self.plan, self.config, meta, slots,
            caveat_plan=self.caveat_plan, jit=False,
            axis=MODEL_AXIS, model_size=self.model_size,
            routed=routed,
        )
        spec_of = self._spec_fn_for(meta)
        arr_spec = {k: spec_of(k) for k in arr_keys}
        qctx_spec = {k: P() for k in ("vi", "vf", "pr", "host")}
        batch_axis = MODEL_AXIS if routed else DATA_AXIS
        in_specs = (
            arr_spec, P(), P(),  # arrays, tid_map, now
            P(None, batch_axis),  # packed query matrix (flat.QM_LAYOUT)
            qctx_spec,
        )
        fn = jax.jit(
            shard_map(
                raw, mesh=self.mesh, in_specs=in_specs,
                out_specs=(P(batch_axis),) * 3,
                check_vma=False,
            )
        )
        while len(self._flat_sharded_fns) >= self.FLAT_FN_CACHE_MAX:
            self._flat_sharded_fns.pop(next(iter(self._flat_sharded_fns)))
        self._flat_sharded_fns[key] = fn
        return fn

    def _routable(self, meta, slots) -> bool:
        """A batch owner-routes iff every root probe a query can make is
        local on its owner shard: all slots are either fully folded
        permissions (pf probe pair) or bare relation leaves (dynamic
        e/KU sites keyed by the query's own (k1, k2)); wildcard edges
        probe a SECOND e/pf bucket whose owner differs, so worlds with
        them keep the psum path.  T-probing slots (meta.t_slots) are
        unroutable too: the T join is model-split under part-serve and
        its bucket geometry differs from the routing geometry, so only
        the psum path's ownership-mask probe is exact there (the KU
        walk those slots compile alongside probes whole-resident
        membership tables and stays local)."""
        if meta.has_wc_edges or meta.pf_haswc:
            return False
        if meta.has_tindex and any(s in meta.t_slots for s in slots):
            return False
        dm = meta.delta
        fold_on = bool(meta.fold_pairs) and not (
            dm is not None and dm.pf_off
        )
        folded = frozenset(meta.fold_pairs) if fold_on else frozenset()
        unfolded = {
            s for (tname, _tid, s, _e) in self.plan.topo_programs
            if (tname, s) not in folded
        }
        return all(s not in unfolded for s in slots)

    # -- snapshot preparation: pad every view to a multiple of model_size --
    def prepare(
        self, snap: Snapshot, prev: Optional[DeviceSnapshot] = None
    ) -> DeviceSnapshot:
        """With ``prev`` (the previous revision's sharded DeviceSnapshot),
        try the incremental path first: the bucket-sharded base tables
        stay resident on their shards, and only the small REPLICATED
        ``dl_*`` overlay ships per revision — the multi-host Watch-driven
        re-index costs O(delta), not O(E/M)·M, per revision."""
        if prev is not None:
            out = self._prepare_delta(snap, prev)
            if out is not None:
                return out
        if (
            self.config.use_flat
            and self.config.flat_blockslice
            and self.model_size & (self.model_size - 1) == 0
        ):
            from ..engine.flat import build_flat_arrays_sharded

            built = build_flat_arrays_sharded(
                snap, self.config, self.model_size, plan=self.plan
            )
            if built is not None:
                flat_arrays, flat_meta, fold_state, _cstate = built
                host = dict(flat_arrays)
                host["node_type"] = _pad_payload(
                    snap.node_type, _ceil_pow2(2 * snap.num_nodes), -1
                )
                ectx, strings = self._ectx_tables(snap)
                host.update(ectx)
                arrays = {
                    k: jax.device_put(
                        v, NamedSharding(self.mesh, self._flat_spec_of(k))
                    )
                    for k, v in host.items()
                }
                self.record_device_bytes(arrays)
                tid_map = np.full(
                    max(self.plan.num_schema_types, 1), -1, dtype=np.int32
                )
                for tname, tid in self.compiled.type_ids.items():
                    tid_map[tid] = snap.interner.type_lookup(tname)
                return DeviceSnapshot(
                    revision=snap.revision,
                    arrays=arrays,
                    tid_map=jnp.asarray(tid_map),
                    snapshot=snap,
                    strings=strings,
                    flat_meta=flat_meta,
                    fold_state=fold_state,
                )
        return self._prepare_legacy(snap)

    def prepare_partitioned(self, part) -> DeviceSnapshot:
        """DeviceSnapshot from a bucket-partitioned feed
        (engine/partition.py partition_feed): the O(E) stacked tables
        exist host-side ONLY for this process's owned shards
        (ShardSlices); ``jax.make_array_from_callback`` asks for exactly
        the addressable blocks, so assembling the global arrays never
        materializes the full table on any host.  Replicated tables
        (node types, contexts, dl_* — and the closure-derived stacks,
        which every process builds whole from the replicated membership
        subgraph) ship via the ordinary replicated device_put.

        A ``serve="routed"`` feed (FlatMeta.part_serve) places the
        O(E)-scale point tables (primary, fold, T join) model-split —
        genuinely disjoint per-device slices, O(E/M) HBM each — and
        everything else whole per device, so owner-routed batches
        dispatch with no collectives (``_dispatch_flat_routed``)."""
        from ..engine.partition import ShardSlices

        snap = part.snapshot
        spec_of = self._spec_fn_for(part.meta)
        host = dict(part.arrays)
        host["node_type"] = _pad_payload(
            snap.node_type, _ceil_pow2(2 * snap.num_nodes), -1
        )
        ectx, strings = self._ectx_tables(snap)
        host.update(ectx)
        arrays = {}
        for k, v in host.items():
            sh = NamedSharding(self.mesh, spec_of(k))
            if isinstance(v, ShardSlices):
                cb = v.block_for
            else:
                # replicated / full tables place via the same callback
                # API: device_put of a replicated array onto a process-
                # spanning mesh runs a consistency-assert COLLECTIVE
                # (multihost_utils.assert_equal), which some CPU jaxlib
                # builds cannot execute — the callback path places local
                # buffers directly and is collective-free by design
                cb = (lambda v: lambda index: v[index])(v)
            arrays[k] = jax.make_array_from_callback(v.shape, sh, cb)
        self.record_device_bytes(arrays)
        tid_map = np.full(
            max(self.plan.num_schema_types, 1), -1, dtype=np.int32
        )
        for tname, tid in self.compiled.type_ids.items():
            tid_map[tid] = snap.interner.type_lookup(tname)
        return DeviceSnapshot(
            revision=snap.revision,
            arrays=arrays,
            tid_map=jnp.asarray(tid_map),
            snapshot=snap,
            strings=strings,
            flat_meta=part.meta,
            fold_state=part.fold_state,
        )

    def prepare_snapshot_partitioned(
        self, snap: Snapshot, prev: Optional[DeviceSnapshot] = None
    ) -> DeviceSnapshot:
        """Partitioned (owner-routed) serve from a resident Snapshot —
        the client's ``with_mesh(partitioned=True)`` path: feed the
        snapshot's raw columns through ``partition_feed(serve="routed")``
        and place with ``prepare_partitioned``.  The incremental path
        rides the partitioned base tables like any sharded snapshot;
        worlds the feed declines (keys past the int32 pack) fall back to
        the ordinary sharded prepare."""
        if prev is not None:
            out = self._prepare_delta(snap, prev)
            if out is not None:
                out.source_snapshot = snap
                return out
        from ..engine.partition import partition_feed, snapshot_raw_columns

        raw = snapshot_raw_columns(snap)
        part = partition_feed(
            snap.revision, snap.compiled, snap.interner, raw,
            self.config, self.model_size,
            contexts=snap.contexts, epoch_us=snap.epoch_us,
            plan=self.plan, serve="routed",
        )
        if part is None:
            return self.prepare(snap)
        out = self.prepare_partitioned(part)
        out.source_snapshot = snap
        return out

    def _delta_prev_ok(self, prev: DeviceSnapshot) -> bool:
        # the sharded incremental prepare rides bucket-sharded base tables
        return prev.flat_meta is not None and prev.flat_meta.sharded

    def _place_replicated(self, v: np.ndarray):
        # overlays are delta-sized: replication beats bucket-sharding and
        # lets the kernel probe them without ownership collectives
        return jax.device_put(v, NamedSharding(self.mesh, P()))

    def _prepare_legacy(self, snap: Snapshot) -> DeviceSnapshot:
        host = self._host_arrays(snap)
        # Model-sharded columns must split evenly across model_size (power
        # of two); the base padding is already pow2, so only meshes wider
        # than the smallest bucket need more.  Sorted key columns keep the
        # I32_MAX sentinel so the padded tail sorts last; payload pads are
        # never read through a matching key.
        sorted_keys = {
            "e_rel", "e_res", "e_subj", "e_srel1", "us_rel", "us_res",
            "ms_subj", "mp_subj", "mp_srel", "ar_rel", "ar_res",
        }
        m = max(8, _ceil_pow2(self.model_size, 1))
        for k, v in list(host.items()):
            if self._arr_spec_of(k) == P(MODEL_AXIS) and v.shape[0] % self.model_size:
                size = _ceil_pow2(v.shape[0], m)
                fill = (2**31 - 1) if k in sorted_keys else -1
                out = np.full(size, fill, v.dtype)
                out[: v.shape[0]] = v
                host[k] = out
        ectx, strings = self._ectx_tables(snap)
        host.update(ectx)
        arrays = {}
        for k, v in host.items():
            arrays[k] = jax.device_put(
                v, NamedSharding(self.mesh, self._arr_spec_of(k))
            )
        tid_map = np.full(max(self.plan.num_schema_types, 1), -1, dtype=np.int32)
        for tname, tid in self.compiled.type_ids.items():
            tid_map[tid] = snap.interner.type_lookup(tname)
        return DeviceSnapshot(
            revision=snap.revision,
            arrays=arrays,
            tid_map=jnp.asarray(tid_map),
            snapshot=snap,
            strings=strings,
        )

    # -- batched check: queries partitioned per data-shard ----------------
    def _dispatch_flat(
        self,
        dsnap: DeviceSnapshot,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        now_us: Optional[int],
        fetch: bool = True,
        bucket_min: int = 0,
        span=_trace.NOOP,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Dispatch over the bucket-sharded flat tables: queries partition
        along the data axis; the kernel's probe sites OR-reduce over the
        model axis internally (engine/flat.py make_flat_fn with axis).
        On a partitioned-serve snapshot (FlatMeta.part_serve), batches
        whose slot set is routable are owner-routed instead — each model
        shard evaluates only the queries whose root bucket it owns, with
        no collective in the compiled program."""
        faults.fire("sharded.collective")
        snap = dsnap.snapshot
        D = self.data_size
        B = queries["q_res"].shape[0]

        all_slots = sorted(
            {int(s) for s in np.unique(queries["q_perm"]) if s >= 0}
        )
        meta = dsnap.flat_meta
        if (
            meta.part_serve and D == 1 and fetch
            and self._routable(meta, all_slots)
        ):
            return self._dispatch_flat_routed(
                dsnap, queries, qctx, now_us, all_slots,
                bucket_min=bucket_min, span=span,
            )
        per = _ceil_pow2(
            -(-B // D), max(bucket_min, self.config.batch_bucket_min)
        )
        BP = per * D
        now = jnp.int32(snap.now_rel32(now_us))
        # packed query matrix (flat.QM_LAYOUT): batch rides axis 1, which
        # partitions over the data axis — ONE sharded transfer; the rare
        # multi-chunk path (more distinct permissions than
        # flat_max_slots) ships only the small perm row per chunk and
        # splices it on device
        dsh = NamedSharding(self.mesh, P(None, DATA_AXIS))
        rep = NamedSharding(self.mesh, P())
        qm_dev = jax.device_put(build_qm(queries, BP, dsnap.flat_meta), dsh)
        qctx_dev = {k: jax.device_put(v, rep) for k, v in qctx.items()}
        arr_keys = tuple(sorted(dsnap.arrays.keys()))
        # batches with more distinct permissions than flat_max_slots are
        # evaluated in slot chunks (each query's slot lives in exactly one
        # chunk; masked-out queries read -1 → all-false) — the compile
        # cost stays bounded instead of unrolling one program per slot
        cap = max(self.config.flat_max_slots, 1)
        q_perm = queries["q_perm"]
        multi = len(all_slots) > cap
        if multi:
            row_sh = NamedSharding(self.mesh, P(DATA_AXIS))
            # one jitted splice per engine: a fresh jax.jit here would
            # retrace on every multi-chunk dispatch.  BOTH slot-bearing
            # rows splice — leaving row 7 (dense q_perm_k1) unmasked
            # would let masked-out queries drive the dynamic leaf in
            # every chunk and OR in spurious overflow flags
            set_perm = self.__dict__.get("_set_perm_fn")
            if set_perm is None:
                set_perm = jax.jit(
                    lambda q, pc, pk: q.at[1].set(pc).at[7].set(pk),
                    out_shardings=dsh,
                )
                self._set_perm_fn = set_perm
            from ..engine.flat import _dense_np

            k1d = _dense_np(dsnap.flat_meta.k1_dense)
        d = p = ovf = None
        with _trace.stage("engine.enqueue", span):
            for at in range(0, max(len(all_slots), 1), cap):
                chunk = tuple(all_slots[at : at + cap])
                if multi:
                    pc = np.full(BP, -1, np.int32)
                    pc[:B] = np.where(
                        np.isin(q_perm, np.asarray(chunk, np.int32)),
                        q_perm, -1,
                    )
                    pk = np.where(
                        pc >= 0, k1d[np.clip(pc, 0, k1d.shape[0] - 1)], -1
                    ).astype(np.int32)
                    qmc = set_perm(
                        qm_dev,
                        jax.device_put(pc, row_sh),
                        jax.device_put(pk, row_sh),
                    )
                else:
                    qmc = qm_dev
                fn = self._flat_sharded_fn(chunk, dsnap.flat_meta, arr_keys)
                cd, cp, covf = fn(
                    dsnap.arrays, dsnap.tid_map, now, qmc, qctx_dev,
                )
                d = cd if d is None else d | cd
                p = cp if p is None else p | cp
                ovf = covf if ovf is None else ovf | covf
        if not fetch:
            return d, p, ovf
        with _trace.stage("engine.fetch", span):
            d, p, ovf = jax.device_get((d, p, ovf))
        return d[:B], p[:B], ovf[:B]

    def _dispatch_flat_routed(
        self,
        dsnap: DeviceSnapshot,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        now_us: Optional[int],
        all_slots,
        bucket_min: int = 0,
        span=_trace.NOOP,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Owner-routed dispatch over a partitioned-serve snapshot: each
        query is hashed by its root (k1, k2) bucket on the HOST and
        grouped to its owner shard before H2D, so each device dispatches
        only against its owned primary/fold slices — O(E/M) HBM per
        device — and the compiled program contains no collective (the
        membership/group tables are whole per device; engine/flat.py
        make_flat_fn routed=True).  Folded-slot queries route by the pf
        geometry, everything else by the primary geometry — same mix32,
        different modulus.  The model-split T join is never probed here:
        _routable keeps T-probing slots on the psum path."""
        import time as _time

        from ..engine.flat import QM_ROWS, _dense_np
        from ..engine.hash import mix32
        from ..engine.partition import shard_owner
        from ..utils import metrics as _metrics

        meta = dsnap.flat_meta
        M = self.model_size
        B = queries["q_res"].shape[0]
        _t0 = _time.perf_counter()
        qmh = build_qm(queries, B, meta)  # [8, B] dense-mapped host matrix
        k1 = (qmh[7].astype(np.int64) * meta.N + qmh[0]).astype(np.int32)
        k2 = (qmh[2].astype(np.int64) * meta.S1 + qmh[3]).astype(np.int32)
        h = mix32([k1, k2], np)
        e_size = (
            int(dsnap.arrays["eh_off"].shape[0]) // M - 1
        ) * M
        owner = shard_owner(h, e_size, M).astype(np.int64)
        pf_slots = sorted({s for _, s in meta.fold_pairs})
        if pf_slots and "pfh_off" in dsnap.arrays:
            pf_size = (
                int(dsnap.arrays["pfh_off"].shape[0]) // M - 1
            ) * M
            pf_owner = shard_owner(h, pf_size, M).astype(np.int64)
            is_pf = np.isin(qmh[1], np.asarray(pf_slots, np.int32))
            owner = np.where(is_pf, pf_owner, owner)
        # invalid / self queries probe nothing that needs locality
        owner = np.where((qmh[0] < 0) | (qmh[1] < 0), 0, owner)
        counts = np.bincount(owner, minlength=M)
        per = _ceil_pow2(
            int(counts.max()), max(bucket_min, self.config.batch_bucket_min)
        )
        order = np.argsort(owner, kind="stable")
        starts = np.cumsum(counts) - counts
        pos = np.arange(B, dtype=np.int64) - np.repeat(starts, counts)
        dst = np.empty(B, np.int64)
        dst[order] = owner[order] * per + pos
        qm_r = np.full((QM_ROWS, M * per), -1, np.int32)
        qm_r[3] = qm_r[6] = 0
        qm_r[:, dst] = qmh
        route_s = _time.perf_counter() - _t0
        _metrics.default.observe("dispatch.route_s", route_s)
        span.event(
            "route",
            shard_batches=[int(c) for c in counts],
            pad_per_shard=int(per),
            exchange_bytes=int(qm_r.nbytes),
        )

        # NOTE: no faults.fire here — _dispatch_flat already fired
        # "sharded.collective" for this dispatch before routing; firing
        # again would double-count injections on the routed path
        now = jnp.int32(dsnap.snapshot.now_rel32(now_us))
        dsh = NamedSharding(self.mesh, P(None, MODEL_AXIS))
        rep = NamedSharding(self.mesh, P())
        qctx_dev = {k: jax.device_put(v, rep) for k, v in qctx.items()}
        arr_keys = tuple(sorted(dsnap.arrays.keys()))
        cap = max(self.config.flat_max_slots, 1)
        k1d = _dense_np(meta.k1_dense)
        d = p = ovf = None
        with _trace.stage("engine.enqueue", span):
            for at in range(0, max(len(all_slots), 1), cap):
                chunk = tuple(all_slots[at : at + cap])
                if len(all_slots) > cap:
                    # multi-chunk: splice the slot rows on the ROUTED
                    # layout host-side (rare path — distinct
                    # permissions > cap)
                    qmc_h = qm_r.copy()
                    pc = qm_r[1]
                    keep = np.isin(pc, np.asarray(chunk, np.int32))
                    qmc_h[1] = np.where(keep, pc, -1)
                    qmc_h[7] = np.where(
                        keep & (pc >= 0),
                        k1d[np.clip(pc, 0, k1d.shape[0] - 1)], -1,
                    ).astype(np.int32)
                    qm_dev = jax.device_put(qmc_h, dsh)
                else:
                    qm_dev = jax.device_put(qm_r, dsh)
                fn = self._flat_sharded_fn(chunk, meta, arr_keys, routed=True)
                cd, cp, covf = fn(
                    dsnap.arrays, dsnap.tid_map, now, qm_dev, qctx_dev,
                )
                d = cd if d is None else d | cd
                p = cp if p is None else p | cp
                ovf = covf if ovf is None else ovf | covf
        with _trace.stage("engine.fetch", span):
            d, p, ovf = jax.device_get((d, p, ovf))
        span.event("unroute")
        return (
            np.asarray(d)[dst], np.asarray(p)[dst], np.asarray(ovf)[dst]
        )

    def _dispatch_columns(
        self,
        dsnap: DeviceSnapshot,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        now_us: Optional[int],
        fetch: bool = True,
        bucket_min: int = 0,
        span=_trace.NOOP,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Partition query columns across the data axis, compute per-shard
        unique (subject, context) closure rows, and dispatch the
        shard_mapped check.  ``queries`` holds length-B columns (q_res,
        q_perm, q_subj, q_srel, q_wc, q_ctx, q_self); q_row is derived
        here per shard.  With ``fetch=False`` the raw padded sharded
        device outputs (length BP ≥ B) are returned for pipelined
        dispatch, mirroring DeviceEngine.check_columns.  A sampled
        ``span`` records a ``sharded.dispatch`` child holding the
        ``engine.enqueue`` / ``engine.fetch`` stages."""
        faults.fire("sharded.dispatch")
        ssp = span.child(
            "sharded.dispatch",
            batch=int(queries["q_res"].shape[0]),
            data=self.data_size, model=self.model_size,
        )
        try:
            if dsnap.flat_meta is not None:
                return self._dispatch_flat(
                    dsnap, queries, qctx, now_us, fetch,
                    bucket_min=bucket_min, span=ssp,
                )
            snap = dsnap.snapshot
            D = self.data_size
            B = queries["q_res"].shape[0]
            per = _ceil_pow2(-(-B // D), self.config.batch_bucket_min)
            BP = per * D

            q = {
                k: np.full(BP, -1 if v.dtype != bool else 0, v.dtype)
                for k, v in queries.items()
            }
            for k in q:
                q[k][:B] = queries[k]
            # per-data-shard unique subjects (each shard computes closures only
            # for its own slice of the batch)
            metrics.default.inc("engine.subject_rows")
            ulists = []
            rows = np.zeros(BP, np.int32)
            for s in range(D):
                blk = slice(s * per, (s + 1) * per)
                uniq, rows[blk] = subject_rows(
                    q["q_subj"][blk], q["q_srel"][blk], q["q_wc"][blk],
                    q["q_ctx"][blk],
                )
                ulists.append(uniq)
            UP = _ceil_pow2(max(u.shape[0] for u in ulists), self.config.batch_bucket_min)
            u = np.full((D * UP, 4), -1, np.int32)
            for s, uniq in enumerate(ulists):
                u[s * UP : s * UP + uniq.shape[0]] = uniq
            ssp.event("stage.partition")

            faults.fire("sharded.collective")
            now = jnp.int32(snap.now_rel32(now_us))
            dsh = NamedSharding(self.mesh, P(DATA_AXIS))
            rep = NamedSharding(self.mesh, P())

            def put(a):
                return jax.device_put(a, dsh)

            with _trace.stage("engine.enqueue", ssp):
                d, p, ovf = self._fn(
                    dsnap.arrays, dsnap.tid_map, now,
                    put(u[:, 0]), put(u[:, 1]), put(u[:, 2]), put(u[:, 3]),
                    put(q["q_res"]), put(q["q_perm"]), put(q["q_subj"]),
                    put(q["q_srel"]), put(q["q_wc"]), put(rows), put(q["q_self"]),
                    put(q["q_ctx"]),
                    {k: jax.device_put(v, rep) for k, v in qctx.items()},
                )
            if not fetch:
                return d, p, ovf
            with _trace.stage("engine.fetch", ssp):
                d, p, ovf = jax.device_get((d, p, ovf))
            return d[:B], p[:B], ovf[:B]
        finally:
            ssp.end()

    def check_batch(
        self,
        dsnap: DeviceSnapshot,
        rels: Sequence[Relationship],
        *,
        now_us: Optional[int] = None,
        latency: bool = False,  # accepted for Client parity; the latency
        # path is single-chip (engine/latency.py), so it's ignored here
        span=_trace.NOOP,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if not rels:
            z = np.zeros(0, bool)
            return z, z, z
        queries, qctx = self._lower_queries(
            dsnap.snapshot, rels, dsnap.strings, span=span
        )
        return self._dispatch_columns(dsnap, queries, qctx, now_us, span=span)

    # -- owner-routed lookup hops (engine/spmv.py frontier SpMV) ----------
    def lookup_hops_for(self, dsnap: DeviceSnapshot, kern):
        """The sharded hop backend of the lookup frontier engine: each
        hop's frontier keys are grouped to their OWNER shard host-side
        (high bits of the reverse-index bucket — only owner-crossing
        IDs move), and the single-shard probe/emit bodies run
        shard_mapped over the model axis with no collective (inside a
        shard the stacked off/table blocks have exactly the
        single-chip shapes, so the bodies are shared verbatim)."""
        return _ShardedLookupHops(self, dsnap, kern)

    def check_columns(
        self,
        dsnap: DeviceSnapshot,
        q_res: np.ndarray,
        q_perm: np.ndarray,
        q_subj: np.ndarray,
        *,
        q_srel: Optional[np.ndarray] = None,
        q_wc: Optional[np.ndarray] = None,
        q_ctx: Optional[np.ndarray] = None,
        qctx_rows=None,
        now_us: Optional[int] = None,
        fetch: bool = True,
        bucket_min: int = 0,
        span=_trace.NOOP,
    ):
        """Columnar bulk check with the sharded layout (the base-class fast
        path assumes an unsharded q_row/uniq table, which would be wrong
        under shard_map — see _dispatch_columns).  ``bucket_min`` raises
        the per-data-shard padding floor, matching DeviceEngine."""
        queries, qctx = self._columns_preamble(
            dsnap, q_res, q_perm, q_subj, q_srel, q_wc, q_ctx, qctx_rows,
            span=span,
        )
        return self._dispatch_columns(
            dsnap, queries, qctx, now_us, fetch=fetch, bucket_min=bucket_min,
            span=span,
        )


# ---------------------------------------------------------------------------
# owner-routed lookup hops (engine/spmv.py frontier SpMV over the
# bucket-sharded reverse-CSR tables)
# ---------------------------------------------------------------------------


class _ShardedLookupHops:
    """One DeviceSnapshot's routed hop executor.  A hop:

    1. HOST: owner of each frontier key = high bits of its reverse-index
       bucket (the partition discipline of engine/partition.py) — keys
       group into per-owner blocks, so the only bytes that cross shards
       are the owner-crossing frontier IDs themselves;
    2. DEVICE: the shard_mapped probe body finds each key's contiguous
       run in ITS shard's block (local bucket = low bits — the stacked
       layout guarantees a key's rows live wholly on its owner), then
       budgeted emission kernels stream the matches per shard, each
       shard walking its own chunk cursor;
    3. HOST: merged live rows feed the frontier engine exactly like the
       single-chip path (engine/spmv.py FrontierState).

    The compiled programs contain NO collective — routing made every
    probe local by construction, mirroring _dispatch_flat_routed."""

    #: probe-argument table per hop kind: (off key, rows-table key)
    _TABS = {
        "rv": ("rv_off", "rvx"),
        "ra": ("ra_off", "rax"),
        "fw": ("fw_off", "fwx"),
        "arg": ("arr_off", "argx"),
    }

    def __init__(self, engine: ShardedEngine, dsnap: DeviceSnapshot, kern):
        self.engine = engine
        self.dsnap = dsnap
        self.kern = kern
        self.M = engine.model_size
        self.mesh = engine.mesh
        self._fns: Dict = engine.__dict__.setdefault("_lookup_hop_fns", {})
        self._dummy = jnp.zeros(1, jnp.int32)

    def _fn_pair(self, kind: str):
        """(runs_fn, emit_fn) shard_mapped over the model axis, cached
        per (meta, kind) on the engine."""
        key = (self.dsnap.flat_meta, kind)
        got = self._fns.get(key)
        if got is not None:
            return got
        MP = P(MODEL_AXIS)
        runs = jax.jit(shard_map(
            self.kern.raw_runs[kind], mesh=self.mesh,
            in_specs=(MP, P(), MP, MP), out_specs=(MP, MP),
            check_vma=False,
        ))
        body = self.kern.raw_emits[kind]
        CH = self.kern.CH  # fixed chunk per shard (static under jit)
        emit = jax.jit(shard_map(
            lambda t, l, n, c0, nw: body(t, l, n, c0, nw, CH),
            mesh=self.mesh,
            in_specs=(MP, MP, MP, MP, P()), out_specs=(MP, MP),
            check_vma=False,
        ))
        got = (runs, emit)
        while len(self._fns) >= 16:
            self._fns.pop(next(iter(self._fns)))
        self._fns[key] = got
        return got

    def expand(self, kind: str, keys: np.ndarray, now):
        """Generator of live row blocks for ``keys`` over one view —
        the sharded mirror of FrontierKernels.expand."""
        from ..engine.hash import mix32 as _mix
        from ..engine.spmv import _mt
        from ..utils import faults as _faults

        if keys.shape[0] == 0:
            return
        _faults.fire("lookup.dispatch")
        arrs = self.dsnap.arrays
        off_key, tbl_key = self._TABS[kind]
        off, tbl = arrs[off_key], arrs[tbl_key]
        # emission gathers rows from the arx view for arrow hops (the
        # group table only resolves ranges)
        emit_tbl = arrs["arx"] if kind == "arg" else tbl
        M = self.M
        bpd = off.shape[0] // M - 1
        size = bpd * M
        kk = np.ascontiguousarray(keys, np.int32)
        h = _mix([kk], np)
        owner = ((h & np.uint32(size - 1)) >> np.uint32(
            bpd.bit_length() - 1
        )).astype(np.int64)
        counts = np.bincount(owner, minlength=M)
        per = 1 << max(int(counts.max()) - 1, 0).bit_length()
        per = max(per, self.kern.F_min)
        routed = np.full(M * per, -1, np.int32)
        order = np.argsort(owner, kind="stable")
        starts = np.cumsum(counts) - counts
        # rank within the owner group, aligned with the sorted order
        rank = np.arange(kk.shape[0], dtype=np.int64) - np.repeat(
            starts, counts
        )
        routed[owner[order] * per + rank] = kk[order]
        runs_fn, emit_fn = self._fn_pair(kind)
        lo, ln = runs_fn(off, self._dummy, tbl, jnp.asarray(routed))
        _mt.inc("lookup.hops")
        totals = np.asarray(ln).reshape(M, per).sum(axis=1)
        CH = self.kern.CH
        at = np.zeros(M, np.int64)
        nowj = jnp.asarray(now)
        while bool((at < totals).any()):
            rows, live = emit_fn(
                emit_tbl, lo, ln, jnp.asarray(at.astype(np.int32)), nowj
            )
            rows, live = jax.device_get((rows, live))
            got = rows[live]
            if got.shape[0]:
                yield got
            at = np.minimum(at + CH, totals)
