"""The JAX device engine: batched two-phase permission checks.

This is the component that replaces the server-side evaluation behind the
reference's ``CheckBulkPermissions`` RPC (client/client.go:238-266): the
batch axis of that RPC becomes the ``vmap`` axis here, and the graph walk
SpiceDB does across its dispatch cluster becomes two static-shape phases
over the snapshot's sorted int32 columns:

- **Phase A — subject closure** (vmapped over the *unique* subjects of the
  batch): a capped frontier walk over the membership (group-nesting) CSR
  computes every userset the subject transitively belongs to, as a sorted
  (node, relation) pair list.  Seeds come from the subject's direct
  membership edges and its type's wildcard node; propagation follows
  userset edges.  With caveats present, two closures are kept — definite
  and possible — mirroring SpiceDB's CONDITIONAL permissionship.

- **Phase B — resource subgraph + fixpoint** (vmapped over queries): a
  capped BFS over tupleset (arrow) edges collects the nodes the resource
  can reach, then relation leaf tests (exact-match binary searches +
  userset-closure probes) seed a dense boolean table V[node, slot] and the
  schema's permission programs — lowered at WriteSchema time to static
  expression IR — iterate to a fixpoint in topological order.

Everything is int32; composite keys are compared lexicographically in a
custom binary search (TPU has no native int64).  Every static cap has an
overflow flag; overflowing queries are re-checked by the host oracle, so
caps bound device work without affecting correctness.

All control flow is static or ``lax`` primitives: the whole check is one
XLA program, traced once per (schema, config, shape bucket).
"""

from __future__ import annotations

from dataclasses import dataclass
from itertools import repeat
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..caveats.device import (
    CaveatDevicePlan,
    build_caveat_plan,
    dedup_contexts,
    encode_contexts,
    make_tri_fn,
)
from ..native import lower as _native_lower
from ..native.interner import NativeInterner
from ..rel.relationship import Relationship
from ..schema.compiler import CompiledSchema
from ..store.snapshot import Snapshot
import time as _time

from ..utils import faults, metrics
from ..utils import perf as _perf
from ..utils import trace as _trace
from ..utils.context import background as _background
from ..utils.errors import classify_dispatch_exception
from ..utils.retry import retry_retriable_errors
from .plan import DevicePlan, EngineConfig, build_plan

#: edge-count floor for the prepare-time lookup-index prewarm thread:
#: small worlds build the index in microseconds inside the first lookup
LOOKUP_PREWARM_MIN_EDGES = 65_536

I32_MAX = 2**31 - 1


def _ceil_pow2(n: int, minimum: int = 8) -> int:
    m = minimum
    while m < n:
        m <<= 1
    return m


def _pad_sorted(a: np.ndarray, size: int) -> np.ndarray:
    """Pad a sorted key column with I32_MAX sentinels."""
    out = np.full(size, I32_MAX, dtype=np.int32)
    out[: a.shape[0]] = a
    return out


def _pad_payload(a: np.ndarray, size: int, fill: int = 0) -> np.ndarray:
    out = np.full(size, fill, dtype=np.int32)
    out[: a.shape[0]] = a
    return out


def subject_rows(
    q_subj: np.ndarray, q_srel: np.ndarray, q_wc: np.ndarray, q_ctx: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """The unique (subject, subject relation, wildcard node, query
    context) rows of a batch for Phase A, and each query's row in them —
    context is part of the key because caveat gates make closures
    context-dependent.  ``(uniq int32[U, 4], q_row int32[B])``: the rows
    in lexicographic order and the inverse, as numpy's row-wise unique
    over the stacked ``[B, 4]`` key returns them, by one ``np.lexsort``
    and a neighbour difference.  Only the two-phase ``_fn`` programs
    read it (the flat path has no Phase A), so their dispatch builds it
    and the lowering does not."""
    key = np.stack([q_subj, q_srel, q_wc, q_ctx], axis=1).astype(np.int32, copy=False)
    B = key.shape[0]
    order = np.lexsort(key.T[::-1])
    rows = key[order]
    first = np.ones(B, bool)
    np.any(rows[1:] != rows[:-1], axis=1, out=first[1:])
    q_row = np.empty(B, np.int32)
    q_row[order] = np.cumsum(first, dtype=np.int32) - 1
    return rows[first], q_row


# ---------------------------------------------------------------------------
# device helpers (traced)
# ---------------------------------------------------------------------------


def _lex_search(cols: Sequence[jnp.ndarray], qs: Sequence[jnp.ndarray], side: str):
    """Binary search over columns sorted lexicographically; returns the
    insertion index for (qs) with the given side.  Arrays must be padded
    with I32_MAX so the padded tail sorts last."""
    n = cols[0].shape[0]
    steps = max(1, (n - 1).bit_length() + 1)

    def body(_, lohi):
        lo, hi = lohi
        cont = lo < hi  # converged searches must not move (or read past n)
        mid = jnp.clip((lo + hi) // 2, 0, n - 1)
        lt = jnp.bool_(False)
        eq = jnp.bool_(True)
        for c, q in zip(cols, qs):
            v = c[mid]
            lt = lt | (eq & (v < q))
            eq = eq & (v == q)
        go_right = lt | (eq if side == "right" else jnp.bool_(False))
        lo = jnp.where(cont & go_right, mid + 1, lo)
        hi = jnp.where(cont & ~go_right, mid, hi)
        return lo, hi

    lo, _ = lax.fori_loop(0, steps, body, (jnp.int32(0), jnp.int32(n)))
    return lo


def _lex_range2(c1, c2, q1, q2):
    lo = _lex_search((c1, c2), (q1, q2), "left")
    hi = _lex_search((c1, c2), (q1, q2), "right")
    return lo, hi


def _lex_contains2(c1, c2, q1, q2):
    pos = _lex_search((c1, c2), (q1, q2), "left")
    posc = jnp.clip(pos, 0, c1.shape[0] - 1)
    return (c1[posc] == q1) & (c2[posc] == q2)


def _pany(x, axis: Optional[str]):
    """OR-reduce across the edge-shard mesh axis (identity off-mesh).
    This is the all-reduce(OR) closing reachability across shards that
    SURVEY.md §2.5/§5 calls for — XLA lowers it onto ICI."""
    if axis is None:
        return x
    return lax.psum(x.astype(jnp.int32), axis) > 0


def _agather(x, axis: Optional[str]):
    """Gather shard-local candidate blocks from every edge shard along the
    mesh axis, concatenated on a new leading axis (identity off-mesh)."""
    if axis is None:
        return x[None]
    return lax.all_gather(x, axis)


def _gate(cav, ctx, exp, now, plane: str, qctx=None, tri=None, tables=None):
    """Edge admissibility: expired edges grant nothing; caveated edges run
    the on-device CEL VM (caveats/device.py) against stored-over-query
    merged context.  Definite plane requires tri==TRUE; possible plane
    admits tri>=UNKNOWN (conditional → host oracle resolution).  Without a
    tri fn (schema has no caveats) this degrades to the expiry mask."""
    live = (exp == 0) | (exp > now)
    if tri is None:
        if plane == "p":
            return live
        return live & (cav == 0)
    q = jnp.broadcast_to(qctx, jnp.shape(cav)) if jnp.shape(cav) else qctx
    t = tri(cav, ctx, q, tables)
    if plane == "p":
        return live & (t >= 1)
    return live & (t == 2)


def _dedup_truncate(n: jnp.ndarray, r: jnp.ndarray, C: int):
    """Sort (n, r) pairs lexicographically, drop duplicates and I32_MAX
    sentinels, return the first C pairs plus an overflow flag."""
    if n.shape[0] < C:
        pad = C - n.shape[0]
        n = jnp.concatenate([n, jnp.full(pad, I32_MAX, jnp.int32)])
        r = jnp.concatenate([r, jnp.full(pad, I32_MAX, jnp.int32)])
    n_s, r_s = lax.sort((n, r), num_keys=2)
    first = jnp.concatenate(
        [jnp.array([True]), (n_s[1:] != n_s[:-1]) | (r_s[1:] != r_s[:-1])]
    )
    keep = first & (n_s < I32_MAX)
    n_u = jnp.where(keep, n_s, I32_MAX)
    r_u = jnp.where(keep, r_s, I32_MAX)
    n_f, r_f = lax.sort((n_u, r_u), num_keys=2)
    overflow = jnp.sum(keep) > C
    return n_f[:C], r_f[:C], overflow


# ---------------------------------------------------------------------------
# Phase A: subject closure
# ---------------------------------------------------------------------------


def _closure_one(
    arrs, cfg: EngineConfig, plane: str, now, u_subj, u_srel, u_wc,
    u_qctx=-1, tri=None, tables=None,
    axis: Optional[str] = None,
):
    C, SC, P = cfg.closure_size, cfg.seed_cap, cfg.prop_cap
    ms_subj, ms_res, ms_rel = arrs["ms_subj"], arrs["ms_res"], arrs["ms_rel"]
    ms_cav, ms_exp = arrs["ms_caveat"], arrs["ms_exp"]
    ms_ctx = arrs["ms_ctx"]
    mp_subj, mp_srel = arrs["mp_subj"], arrs["mp_srel"]
    mp_res, mp_rel = arrs["mp_res"], arrs["mp_rel"]
    mp_cav, mp_exp = arrs["mp_caveat"], arrs["mp_exp"]
    mp_ctx = arrs["mp_ctx"]

    overflow = jnp.bool_(False)
    # own key: a userset subject is a member of itself
    own = u_srel >= 0
    bufs_n = [jnp.where(own, u_subj, I32_MAX)[None]]
    bufs_r = [jnp.where(own, u_srel, I32_MAX)[None]]
    # direct seeds (only direct-object subjects have direct membership
    # edges; userset subjects enter via their own key + propagation)
    last = max(ms_subj.shape[0] - 1, 0)
    for src0 in (u_subj, u_wc):
        src = jnp.where(u_srel < 0, src0, -1)
        lo = jnp.searchsorted(ms_subj, src, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(ms_subj, src, side="right").astype(jnp.int32)
        overflow |= (hi - lo) > SC
        idx = lo + jnp.arange(SC, dtype=jnp.int32)
        valid = (idx < hi) & (src >= 0)
        idxc = jnp.clip(idx, 0, last)
        keep = valid & _gate(
            ms_cav[idxc], ms_ctx[idxc], ms_exp[idxc], now, plane,
            u_qctx, tri, tables,
        )
        # each edge shard contributes its local seeds; gather + dedup merges
        bufs_n.append(_agather(jnp.where(keep, ms_res[idxc], I32_MAX), axis).ravel())
        bufs_r.append(_agather(jnp.where(keep, ms_rel[idxc], I32_MAX), axis).ravel())
    c_n, c_r, ovf = _dedup_truncate(
        jnp.concatenate(bufs_n), jnp.concatenate(bufs_r), C
    )
    overflow |= ovf

    lastp = max(mp_subj.shape[0] - 1, 0)
    lex_lo = jax.vmap(lambda a, b: _lex_search((mp_subj, mp_srel), (a, b), "left"))
    lex_hi = jax.vmap(lambda a, b: _lex_search((mp_subj, mp_srel), (a, b), "right"))

    def hop(c_n, c_r, overflow):
        lo = lex_lo(c_n, c_r)
        hi = lex_hi(c_n, c_r)
        overflow |= jnp.any((hi - lo) > P)
        idx = lo[:, None] + jnp.arange(P, dtype=jnp.int32)[None, :]
        valid = (idx < hi[:, None]) & (c_n[:, None] < I32_MAX)
        idxc = jnp.clip(idx, 0, lastp)
        keep = valid & _gate(
            mp_cav[idxc], mp_ctx[idxc], mp_exp[idxc], now, plane,
            u_qctx, tri, tables,
        )
        cand_n = _agather(jnp.where(keep, mp_res[idxc], I32_MAX).ravel(), axis).ravel()
        cand_r = _agather(jnp.where(keep, mp_rel[idxc], I32_MAX).ravel(), axis).ravel()
        c_n, c_r, ovf = _dedup_truncate(
            jnp.concatenate([c_n, cand_n]), jnp.concatenate([c_r, cand_r]), C
        )
        return c_n, c_r, overflow | ovf

    for _ in range(cfg.closure_hops):
        c_n, c_r, overflow = hop(c_n, c_r, overflow)
    if cfg.closure_hops > 0:
        # detection pass: if one more hop still grows the closure, the hop
        # cap was insufficient (nesting deeper than closure_hops) — flag it
        # so the caller falls back to the host oracle instead of silently
        # missing memberships
        size_before = jnp.sum(c_n < I32_MAX)
        c_n, c_r, overflow = hop(c_n, c_r, overflow)
        overflow |= jnp.sum(c_n < I32_MAX) > size_before
    return c_n, c_r, _pany(overflow, axis)


# ---------------------------------------------------------------------------
# Phase B: per-query evaluation
# ---------------------------------------------------------------------------


def _query_one(
    arrs,
    plan: DevicePlan,
    cfg: EngineConfig,
    now,
    tid_map,  # int32[num_schema_types] → interner type id
    Cd_n, Cd_r, Cp_n, Cp_r,  # [U, C] closures
    q_res, q_perm, q_subj, q_srel, q_wc, q_row, q_self,
    q_ctx=-1, tri=None, tables=None,
    axis: Optional[str] = None,
):
    N = cfg.subgraph_nodes
    TS = len(plan.ts_slots)
    K = cfg.arrow_fanout
    KU = cfg.us_leaf_cap
    SLOTS = plan.num_slots

    e_rel, e_res = arrs["e_rel"], arrs["e_res"]
    e_subj, e_srel1 = arrs["e_subj"], arrs["e_srel1"]
    e_cav, e_exp, e_ctx = arrs["e_caveat"], arrs["e_exp"], arrs["e_ctx"]
    us_rel, us_res = arrs["us_rel"], arrs["us_res"]
    us_subj, us_srel = arrs["us_subj"], arrs["us_srel"]
    us_cav, us_exp, us_ctx = arrs["us_caveat"], arrs["us_exp"], arrs["us_ctx"]
    ar_rel, ar_res = arrs["ar_rel"], arrs["ar_res"]
    ar_child = arrs["ar_child"]
    ar_cav, ar_exp, ar_ctx = arrs["ar_caveat"], arrs["ar_exp"], arrs["ar_ctx"]
    node_type = arrs["node_type"]

    my_cd_n, my_cd_r = Cd_n[q_row], Cd_r[q_row]
    my_cp_n, my_cp_r = Cp_n[q_row], Cp_r[q_row]

    overflow = jnp.bool_(False)

    # ---- Phase B1: arrow-subgraph BFS --------------------------------
    nodes = jnp.full(N, -1, jnp.int32).at[0].set(q_res)
    count = jnp.where(q_res >= 0, jnp.int32(1), jnp.int32(0))
    TSax = max(TS, 1)
    # with edge sharding, every shard contributes up to K children per
    # (node, tupleset relation); gathered fanout is M*K
    M = 1 if axis is None else lax.axis_size(axis)
    KE = K * M
    child_slot = jnp.full((N, TSax, KE), -1, jnp.int32)
    child_gd = jnp.zeros((N, TSax, KE), bool)
    child_gp = jnp.zeros((N, TSax, KE), bool)

    if TS > 0:
        last_ar = max(ar_rel.shape[0] - 1, 0)
        lo_f = jax.vmap(lambda a, b: _lex_search((ar_rel, ar_res), (a, b), "left"))
        hi_f = jax.vmap(lambda a, b: _lex_search((ar_rel, ar_res), (a, b), "right"))
        # N-1 hops discover a chain of N nodes; the +1 detection hop scans
        # the last-discovered nodes' children so a subgraph deeper than the
        # cap trips the count>=N overflow instead of silently truncating
        for _hop in range(max(N - 1, 1) + 1):
            cand_children = []
            cand_gd = []
            cand_gp = []
            for ts_slot in plan.ts_slots:
                rq = jnp.full(N, ts_slot, jnp.int32)
                nq = jnp.where(nodes >= 0, nodes, I32_MAX)
                lo = lo_f(rq, nq)
                hi = hi_f(rq, nq)
                overflow |= jnp.any((hi - lo) > K)
                idx = lo[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
                valid = (idx < hi[:, None]) & (nodes >= 0)[:, None]
                idxc = jnp.clip(idx, 0, last_ar)
                gd = valid & _gate(
                    ar_cav[idxc], ar_ctx[idxc], ar_exp[idxc], now, "d",
                    q_ctx, tri, tables,
                )
                gp = valid & _gate(
                    ar_cav[idxc], ar_ctx[idxc], ar_exp[idxc], now, "p",
                    q_ctx, tri, tables,
                )
                cand_children.append(jnp.where(valid, ar_child[idxc], -1))
                cand_gd.append(gd)
                cand_gp.append(gp)
            cc = jnp.stack(cand_children)  # [TS, N, K]
            cgd = jnp.stack(cand_gd)
            cgp = jnp.stack(cand_gp)
            if axis is not None:
                # merge every shard's local candidates: [M, TS, N, K] →
                # [TS, N, M*K]; identical on all shards afterwards, so the
                # slot assignment below is replicated deterministically
                cc = _agather(cc, axis).transpose(1, 2, 0, 3).reshape(TS, N, KE)
                cgd = _agather(cgd, axis).transpose(1, 2, 0, 3).reshape(TS, N, KE)
                cgp = _agather(cgp, axis).transpose(1, 2, 0, 3).reshape(TS, N, KE)

            def assign(carry, c):
                nodes_, count_, ovf_ = carry
                valid = c >= 0
                eq = nodes_ == c
                found = jnp.any(eq)
                slot_found = jnp.argmax(eq).astype(jnp.int32)
                can_add = valid & ~found & (count_ < N)
                added = nodes_.at[jnp.clip(count_, 0, N - 1)].set(c)
                nodes_ = jnp.where(can_add, added, nodes_)
                slot = jnp.where(
                    valid,
                    jnp.where(found, slot_found, jnp.where(can_add, count_, -1)),
                    jnp.int32(-1),
                )
                ovf_ = ovf_ | (valid & ~found & (count_ >= N))
                count_ = count_ + can_add.astype(jnp.int32)
                return (nodes_, count_, ovf_), slot

            (nodes, count, ovf), slots = lax.scan(
                assign, (nodes, count, jnp.bool_(False)), cc.ravel()
            )
            overflow |= ovf
            child_slot = slots.reshape(TS, N, KE).transpose(1, 0, 2)
            child_gd = cgd.transpose(1, 0, 2)
            child_gp = cgp.transpose(1, 0, 2)

    # ---- Phase B2: relation leaf tests --------------------------------
    last_e = max(e_rel.shape[0] - 1, 0)
    last_us = max(us_rel.shape[0] - 1, 0)
    CW = my_cd_n.shape[0]

    def leaf(node, rel_slot):
        exists = node >= 0
        node_k = jnp.where(exists, node, I32_MAX)
        # direct subject
        pos = _lex_search(
            (e_rel, e_res, e_subj, e_srel1),
            (rel_slot, node_k, q_subj, q_srel + 1),
            "left",
        )
        posc = jnp.clip(pos, 0, last_e)
        hit = (
            exists
            & (q_subj >= 0)
            & (e_rel[posc] == rel_slot)
            & (e_res[posc] == node)
            & (e_subj[posc] == q_subj)
            & (e_srel1[posc] == q_srel + 1)
        )
        d = hit & _gate(
            e_cav[posc], e_ctx[posc], e_exp[posc], now, "d", q_ctx, tri, tables
        )
        p = hit & _gate(
            e_cav[posc], e_ctx[posc], e_exp[posc], now, "p", q_ctx, tri, tables
        )
        # wildcard (only grants direct-object subject queries)
        wq = jnp.where((q_wc >= 0) & (q_srel < 0), q_wc, I32_MAX)
        wpos = _lex_search(
            (e_rel, e_res, e_subj, e_srel1), (rel_slot, node_k, wq, jnp.int32(0)), "left"
        )
        wposc = jnp.clip(wpos, 0, last_e)
        whit = (
            exists
            & (wq < I32_MAX)
            & (e_rel[wposc] == rel_slot)
            & (e_res[wposc] == node)
            & (e_subj[wposc] == wq)
            & (e_srel1[wposc] == 0)
        )
        d |= whit & _gate(
            e_cav[wposc], e_ctx[wposc], e_exp[wposc], now, "d", q_ctx, tri, tables
        )
        p |= whit & _gate(
            e_cav[wposc], e_ctx[wposc], e_exp[wposc], now, "p", q_ctx, tri, tables
        )
        # userset grants probed against the subject closure
        lo, hi = _lex_range2(us_rel, us_res, rel_slot, node_k)
        ovf = (hi - lo) > KU
        idx = lo + jnp.arange(KU, dtype=jnp.int32)
        valid = (idx < hi) & exists
        idxc = jnp.clip(idx, 0, last_us)
        in_d = jax.vmap(
            lambda s, r: _lex_contains2(my_cd_n, my_cd_r, s, r)
        )(us_subj[idxc], us_srel[idxc])
        in_p = jax.vmap(
            lambda s, r: _lex_contains2(my_cp_n, my_cp_r, s, r)
        )(us_subj[idxc], us_srel[idxc])
        if plan.has_permission_usersets:
            # permission-valued usersets: membership is the permission
            # fixpoint the device doesn't run — the grant is possible
            # (→ per-query host resolution), never device-definite.  Same
            # for relation usersets whose membership may be extended
            # through a permission chain (the static pus pair set).
            permf = arrs["us_perm"][idxc] != 0
            in_pus = jax.vmap(
                lambda s, r: _lex_contains2(arrs["pus_n"], arrs["pus_r"], s, r)
            )(us_subj[idxc], us_srel[idxc])
            in_d = in_d & ~permf
            in_p = in_p | in_pus | permf
        d |= jnp.any(valid & in_d & _gate(
            us_cav[idxc], us_ctx[idxc], us_exp[idxc], now, "d", q_ctx, tri, tables
        ))
        p |= jnp.any(valid & in_p & _gate(
            us_cav[idxc], us_ctx[idxc], us_exp[idxc], now, "p", q_ctx, tri, tables
        ))
        return d, p, ovf

    rs = jnp.asarray(plan.rel_leaf_slots, dtype=jnp.int32)
    if rs.shape[0] == 0:
        rs = jnp.zeros(1, jnp.int32)
    leaf_d, leaf_p, leaf_ovf = jax.vmap(
        lambda n: jax.vmap(lambda r: leaf(n, r))(rs)
    )(nodes)
    # merge shard-local leaf hits: a direct/wildcard/userset grant may live
    # on any edge shard
    leaf_d = _pany(leaf_d, axis)
    leaf_p = _pany(leaf_p, axis)
    overflow |= jnp.any(leaf_ovf & (nodes >= 0)[:, None])

    V_d = jnp.zeros((N, SLOTS), bool)
    V_p = jnp.zeros((N, SLOTS), bool)
    for ri, slot in enumerate(plan.rel_leaf_slots):
        V_d = V_d.at[:, slot].set(leaf_d[:, ri])
        V_p = V_p.at[:, slot].set(leaf_p[:, ri])

    # ---- Phase B3: fixpoint over permission programs -------------------
    ntype = jnp.where(nodes >= 0, node_type[jnp.clip(nodes, 0)], -1)

    def eval_expr(ir, V_d, V_p):
        tag = ir[0]
        if tag == "ref":
            s = ir[1]
            return V_d[:, s], V_p[:, s]
        if tag == "nil":
            z = jnp.zeros(N, bool)
            return z, z
        if tag == "arrow":
            ti, rslot = ir[1], ir[2]
            cs = child_slot[:, ti, :]
            valid = cs >= 0
            csc = jnp.clip(cs, 0)
            d = jnp.any(V_d[csc, rslot] & valid & child_gd[:, ti, :], axis=-1)
            p = jnp.any(V_p[csc, rslot] & valid & child_gp[:, ti, :], axis=-1)
            return d, p
        if tag == "union":
            d = jnp.zeros(N, bool)
            p = jnp.zeros(N, bool)
            for c in ir[1]:
                cd, cp = eval_expr(c, V_d, V_p)
                d, p = d | cd, p | cp
            return d, p
        if tag == "inter":
            d = jnp.ones(N, bool)
            p = jnp.ones(N, bool)
            for c in ir[1]:
                cd, cp = eval_expr(c, V_d, V_p)
                d, p = d & cd, p & cp
            return d, p
        if tag == "excl":
            bd, bp = eval_expr(ir[1], V_d, V_p)
            sd, sp = eval_expr(ir[2], V_d, V_p)
            # Kleene: definitely granted iff base definite and subtracted
            # definitely absent; possible iff base possible and subtracted
            # not definite.
            return bd & ~sp, bp & ~sd
        raise TypeError(f"bad expression IR {ir!r}")

    def iteration(_, carry):
        V_d, V_p = carry
        for (_tname, tid, slot, expr) in plan.topo_programs:
            itid = tid_map[tid]
            mask = (ntype == itid) & (nodes >= 0)
            d, p = eval_expr(expr, V_d, V_p)
            V_d = V_d.at[:, slot].set(jnp.where(mask, d, V_d[:, slot]))
            V_p = V_p.at[:, slot].set(jnp.where(mask, p, V_p[:, slot]))
        return V_d, V_p

    if plan.topo_programs:
        V_d, V_p = lax.fori_loop(0, cfg.eval_iters, iteration, (V_d, V_p))

    valid_q = (q_res >= 0) & (q_perm >= 0)
    perm_c = jnp.clip(q_perm, 0, SLOTS - 1)
    d = (V_d[0, perm_c] & valid_q) | q_self
    p = (V_p[0, perm_c] & valid_q) | q_self
    return d, p, _pany(overflow, axis)


# ---------------------------------------------------------------------------
# the jitted whole-batch function
# ---------------------------------------------------------------------------


def _make_check_fn(plan: DevicePlan, cfg: EngineConfig,
                   axis: Optional[str] = None, jit: bool = True,
                   caveat_plan: Optional[CaveatDevicePlan] = None):
    """Build the whole-batch check function.  With ``axis`` set, the
    function is written for shard_map over that mesh axis: edge arrays are
    shard-local and collectives merge at every gather/test point.  With a
    caveat plan, the on-device CEL VM gates caveated edges against merged
    stored/query context (qctx tables ride along as batch inputs)."""

    tri = make_tri_fn(caveat_plan) if caveat_plan is not None else None

    def fn(arrs, tid_map, now, u_subj, u_srel, u_wc, u_qctx,
           q_res, q_perm, q_subj, q_srel, q_wc, q_row, q_self, q_ctx, qctx):
        if tri is not None:
            tables = {
                "ectx_vi": arrs["ectx_vi"], "ectx_vf": arrs["ectx_vf"],
                "ectx_pr": arrs["ectx_pr"], "ectx_host": arrs["ectx_host"],
                "qctx_vi": qctx["vi"], "qctx_vf": qctx["vf"],
                "qctx_pr": qctx["pr"], "qctx_host": qctx["host"],
            }
        else:
            tables = None
        close_p = jax.vmap(
            lambda s, r, w, qc: _closure_one(
                arrs, cfg, "p", now, s, r, w, qc, tri, tables, axis
            )
        )
        Cp_n, Cp_r, ovf_p = close_p(u_subj, u_srel, u_wc, u_qctx)
        if plan.two_plane:
            close_d = jax.vmap(
                lambda s, r, w, qc: _closure_one(
                    arrs, cfg, "d", now, s, r, w, qc, tri, tables, axis
                )
            )
            Cd_n, Cd_r, ovf_d = close_d(u_subj, u_srel, u_wc, u_qctx)
        else:
            Cd_n, Cd_r, ovf_d = Cp_n, Cp_r, ovf_p

        per_query = jax.vmap(
            lambda a, b, c, d_, e, f, g, qc: _query_one(
                arrs, plan, cfg, now, tid_map,
                Cd_n, Cd_r, Cp_n, Cp_r,
                a, b, c, d_, e, f, g,
                qc, tri, tables,
                axis,
            )
        )
        d, p, ovf_q = per_query(
            q_res, q_perm, q_subj, q_srel, q_wc, q_row, q_self, q_ctx
        )
        u_ovf = ovf_d | ovf_p
        return d, p, ovf_q | u_ovf[q_row]

    return jax.jit(fn) if jit else fn


# ---------------------------------------------------------------------------
# host wrapper
# ---------------------------------------------------------------------------


@dataclass
class DeviceSnapshot:
    """Padded device-resident form of a Snapshot (padded to pow2 buckets so
    jit retraces are bounded)."""

    revision: int
    arrays: Dict[str, jnp.ndarray]
    tid_map: jnp.ndarray  # int32[num_schema_types] → interner type id
    snapshot: Snapshot
    #: string-intern pool for caveat context values (literals + stored
    #: context strings); query-time strings outside it get negative ids
    strings: Optional[Dict[str, int]] = None
    #: static geometry of the flat engine's hash/closure tables (None when
    #: the flat kernel is disabled); see engine/flat.py
    flat_meta: Optional[Any] = None
    #: accumulated host-side delta state since the last FULL prepare (set
    #: on delta-prepared snapshots; engine/flat.py _acc_collapse)
    delta_acc: Optional[Dict[str, np.ndarray]] = None
    #: host-side fold maintenance state (engine/fold.py FoldState), set
    #: at FULL prepare on folded worlds and carried along a delta chain
    #: so each revision's dl_pf* overlay recomputes from (base, acc)
    fold_state: Optional[Any] = None
    #: host-side closure advance state (engine/flat.py ClosureHostState):
    #: set at FULL prepare, ADVANCED each revision by the membership-delta
    #: path (store/closure.py advance_closure) so member-edge writes keep
    #: the flattened closure fresh without a rebuild
    closure_state: Optional[Any] = None
    #: lazily-attached latency-mode dispatcher (engine/latency.py
    #: LatencyPath) — per-snapshot warm state (staging buffers, local
    #: pin table); the executables themselves are shared engine-wide
    latency_path: Optional[Any] = None
    #: the store Snapshot a partitioned prepare was fed from (its
    #: ``snapshot`` is the bucket-filtered view); the client's dsnap
    #: cache identity check consults it
    source_snapshot: Optional[Any] = None
    #: HBM-lean mode keeps the raw O(E) kernel columns HOST-side (the
    #: flat blockslice kernel never reads them; sharded prepares never
    #: shipped them): the rare legacy fallback (a batch with more
    #: distinct permissions than flat_max_slots) ships them lazily once
    #: per snapshot via DeviceEngine._legacy_arrays
    host_arrays: Optional[Dict[str, np.ndarray]] = None
    #: the lazily-shipped legacy argument dict (host_arrays ∪ arrays)
    legacy_cache: Optional[Dict[str, Any]] = None


class DeviceEngine:
    """Compiles a schema into a jitted batched check function and manages
    device-resident snapshots."""

    def __init__(
        self, compiled: CompiledSchema, config: Optional[EngineConfig] = None
    ) -> None:
        self.compiled = compiled
        self.plan = build_plan(compiled)
        self.config = config or EngineConfig.for_schema(compiled)
        self.caveat_plan = (
            build_caveat_plan(compiled) if self.plan.two_plane else None
        )
        self._fn = _make_check_fn(
            self.plan, self.config, caveat_plan=self.caveat_plan
        )
        #: flat-kernel cache: (slots tuple, FlatMeta) → jitted fn
        self._flat_fns: Dict[Any, Any] = {}
        #: pinned latency-mode executables shared across snapshots:
        #: (FlatMeta, array-shape fingerprint, (slots, tier, qctx key))
        #: → AOT-compiled kernel — a Watch delta chain with stable table
        #: geometry re-pins per revision without recompiling.  Guarded by
        #: its own lock: multiple LatencyPaths (concurrent revisions)
        #: share this dict, and the FIFO eviction iterates it
        import threading

        self._latency_pins: Dict[Any, Any] = {}
        self._latency_pins_lock = threading.Lock()
        #: (slots, BP, meta) batch programs already registered with the
        #: perf cost ledger — the per-dispatch path checks this local
        #: set only (no global ledger lock per call)
        self._perf_cost_reg: set = set()
        #: context-free qctx singletons (host + device forms)
        self._empty_qctx_np: Optional[Dict[str, np.ndarray]] = None
        self._empty_qctx_jnp = None

    #: every per-edge/lookup column _host_arrays emits (the sharded engine
    #: derives its shard_map specs from this — keep in lockstep, enforced
    #: by test_sharded.py's key-parity test)
    ARRAY_COLUMN_KEYS = (
        "e_rel", "e_res", "e_subj", "e_srel1", "e_caveat", "e_ctx", "e_exp",
        "us_rel", "us_res", "us_subj", "us_srel", "us_caveat", "us_ctx",
        "us_exp", "us_perm", "pus_n", "pus_r",
        "ms_subj", "ms_res", "ms_rel", "ms_caveat", "ms_ctx", "ms_exp",
        "mp_subj", "mp_srel", "mp_res", "mp_rel", "mp_caveat", "mp_ctx",
        "mp_exp",
        "ar_rel", "ar_res", "ar_child", "ar_caveat", "ar_ctx", "ar_exp",
        "node_type",
    )

    # -- snapshot preparation -------------------------------------------
    def _host_arrays(self, snap: Snapshot) -> Dict[str, np.ndarray]:
        """Padded host-side columns (shared by single-chip and sharded
        prepare paths)."""
        E = _ceil_pow2(snap.e_rel.shape[0])
        US = _ceil_pow2(snap.us_rel.shape[0])
        MS = _ceil_pow2(snap.ms_subj.shape[0])
        MP = _ceil_pow2(snap.mp_subj.shape[0])
        AR = _ceil_pow2(snap.ar_rel.shape[0])
        # 2x headroom: Watch-driven deltas intern fresh nodes, and the
        # delta-prepare reuses this buffer until the bucket would grow
        NN = _ceil_pow2(2 * snap.num_nodes)
        return {
            "e_rel": _pad_sorted(snap.e_rel, E),
            "e_res": _pad_sorted(snap.e_res, E),
            "e_subj": _pad_sorted(snap.e_subj, E),
            "e_srel1": _pad_sorted(snap.e_srel1, E),
            "e_caveat": _pad_payload(snap.e_caveat, E),
            "e_ctx": _pad_payload(snap.e_ctx, E, -1),
            "e_exp": _pad_payload(snap.e_exp, E),
            "us_rel": _pad_sorted(snap.us_rel, US),
            "us_res": _pad_sorted(snap.us_res, US),
            "us_subj": _pad_payload(snap.us_subj, US, -1),
            "us_srel": _pad_payload(snap.us_srel, US, -1),
            "us_caveat": _pad_payload(snap.us_caveat, US),
            "us_ctx": _pad_payload(snap.us_ctx, US, -1),
            "us_exp": _pad_payload(snap.us_exp, US),
            "us_perm": _pad_payload(snap.us_perm, US),
            "pus_n": _pad_sorted(snap.pus_n, _ceil_pow2(snap.pus_n.shape[0])),
            "pus_r": _pad_sorted(snap.pus_r, _ceil_pow2(snap.pus_n.shape[0])),
            "ms_subj": _pad_sorted(snap.ms_subj, MS),
            "ms_res": _pad_payload(snap.ms_res, MS, -1),
            "ms_rel": _pad_payload(snap.ms_rel, MS, -1),
            "ms_caveat": _pad_payload(snap.ms_caveat, MS),
            "ms_ctx": _pad_payload(snap.ms_ctx, MS, -1),
            "ms_exp": _pad_payload(snap.ms_exp, MS),
            "mp_subj": _pad_sorted(snap.mp_subj, MP),
            "mp_srel": _pad_sorted(snap.mp_srel, MP),
            "mp_res": _pad_payload(snap.mp_res, MP, -1),
            "mp_rel": _pad_payload(snap.mp_rel, MP, -1),
            "mp_caveat": _pad_payload(snap.mp_caveat, MP),
            "mp_ctx": _pad_payload(snap.mp_ctx, MP, -1),
            "mp_exp": _pad_payload(snap.mp_exp, MP),
            "ar_rel": _pad_sorted(snap.ar_rel, AR),
            "ar_res": _pad_sorted(snap.ar_res, AR),
            "ar_child": _pad_payload(snap.ar_child, AR, -1),
            "ar_caveat": _pad_payload(snap.ar_caveat, AR),
            "ar_ctx": _pad_payload(snap.ar_ctx, AR, -1),
            "ar_exp": _pad_payload(snap.ar_exp, AR),
            "node_type": _pad_payload(snap.node_type, NN, -1),
        }

    def _ectx_tables(
        self, snap: Snapshot
    ) -> Tuple[Dict[str, np.ndarray], Optional[Dict[str, int]]]:
        """Encode stored caveat contexts into padded device tables."""
        if self.caveat_plan is None:
            return {}, None
        strings = dict(self.caveat_plan.base_strings)
        table = encode_contexts(self.caveat_plan, snap.contexts, strings)
        # 2x headroom: Watch-driven deltas append stored contexts, and the
        # delta-prepare re-encodes in place only while the bucket holds
        NC = _ceil_pow2(2 * max(table.vi.shape[0], 1), 4)

        def padrows(a: np.ndarray, fill=0) -> np.ndarray:
            out = np.full((NC,) + a.shape[1:], fill, a.dtype)
            out[: a.shape[0]] = a
            return out

        return {
            "ectx_vi": padrows(table.vi),
            "ectx_vf": padrows(table.vf),
            "ectx_pr": padrows(table.present),
            "ectx_host": padrows(table.host),
        }, strings

    @staticmethod
    def record_device_bytes(arrays: Mapping[str, Any]) -> int:
        """Publish the resident table footprint: one
        ``snapshot.device_bytes`` gauge plus a per-table breakdown
        (``snapshot.device_bytes.<table>``) — /metrics and trace spans
        then report HBM residency live, not just at bench time."""
        total = 0
        # drop the previous snapshot's per-table entries first: a delta
        # prepare can remove tables (despec'd offset anchors), and a
        # stale gauge would break breakdown-sums-to-total
        metrics.default.clear_gauges("snapshot.device_bytes.")
        for k, v in arrays.items():
            nb = int(getattr(v, "nbytes", 0))
            total += nb
            metrics.default.set_gauge(f"snapshot.device_bytes.{k}", nb)
        metrics.default.set_gauge("snapshot.device_bytes", total)
        _trace.event_if_active("snapshot.device_bytes", total=total)
        return total

    def prepare(
        self, snap: Snapshot, prev: Optional[DeviceSnapshot] = None
    ) -> DeviceSnapshot:
        """Ship a snapshot to the device.  With ``prev`` (the DeviceSnapshot
        of the revision this one was delta-derived from), try the
        incremental path first: base tables stay resident, only small
        ``dl_*`` overlays ship (engine/flat.py build_delta_arrays) — the
        Watch-driven re-index costs O(delta), not O(E), per revision."""
        faults.fire("device.prepare")
        if prev is not None:
            out = self._prepare_delta(snap, prev)
            if out is not None:
                return out
        _t0 = _time.perf_counter()
        with metrics.default.timer("prepare.host_tables_s"):
            arrays = self._host_arrays(snap)
            ectx, strings = self._ectx_tables(snap)
            arrays.update(ectx)
        flat_meta = None
        fold_state = None
        closure_state = None
        host_arrays = None
        if self.config.use_flat:
            from .flat import build_flat_arrays

            built = build_flat_arrays(snap, self.config, plan=self.plan)
            if built is not None:  # unpackable graphs use the legacy path
                flat_arrays, flat_meta, fold_state, closure_state = built
                arrays.update(flat_arrays)
                if self.config.packed_on() and flat_meta.blockslice:
                    # HBM-lean: the blockslice kernel reads none of the
                    # raw O(E) columns — keep them host-side and ship
                    # them lazily iff the legacy fallback ever fires
                    from .packed import narrow_nodes

                    host_arrays = {
                        k: arrays.pop(k)
                        for k in self.ARRAY_COLUMN_KEYS
                        if k != "node_type" and k in arrays
                    }
                    arrays["node_type"] = narrow_nodes(
                        arrays["node_type"], snap.interner.num_types
                    )
        with metrics.default.timer("prepare.h2d_s"):
            # one batched transfer (the runtime can pipeline leaves)
            # instead of per-array jnp.asarray round trips
            arrays = jax.device_put(arrays)
        self.record_device_bytes(arrays)
        tid_map = np.full(max(self.plan.num_schema_types, 1), -1, dtype=np.int32)
        for tname, tid in self.compiled.type_ids.items():
            tid_map[tid] = snap.interner.type_lookup(tname)
        if not self._frontier_will_serve(flat_meta, snap):
            # snapshots carrying the reverse-CSR index (within the
            # frontier's seen-set budget) answer lookups on the device
            # frontier path (engine/spmv.py) — the O(E log E) transposed
            # host index would be dead weight there; everything else
            # still walker-serves and wants the background build
            self._maybe_prewarm_walker_index(snap)
        metrics.default.observe(
            "prepare.total_s", _time.perf_counter() - _t0
        )
        dsnap = DeviceSnapshot(
            revision=snap.revision,
            arrays=arrays,
            tid_map=jnp.asarray(tid_map),
            snapshot=snap,
            strings=strings,
            flat_meta=flat_meta,
            fold_state=fold_state,
            closure_state=closure_state,
            host_arrays=host_arrays,
        )
        # perf ledger: publish the gathered-bytes model (per-level,
        # per-table) for this snapshot — the roofline numerator rides
        # /metrics and incident bundles from the moment of prepare
        _perf.publish_model(dsnap)
        return dsnap

    @staticmethod
    def _frontier_will_serve(flat_meta, snap) -> bool:
        """Whether lookups on this snapshot take the device frontier
        path (engine/spmv.py) — ONE shared predicate with frontier_ok's
        static half, so the prewarm decision cannot drift from the
        actual lookup routing."""
        from .spmv import frontier_static_ok

        return frontier_static_ok(flat_meta, snap)

    def _maybe_prewarm_walker_index(self, snap: Snapshot) -> None:
        """Build the transposed lookup index off-thread (numpy sorts
        release the GIL): the first walker-served lookup_resources at
        1M+ docs then joins a mostly-finished build instead of paying
        the whole O(E log E) sort inside a user-facing query.  One
        in-flight build per engine — a Watch chain of delta prepares
        must not stack O(E log E) threads (once the first build lands,
        the chain-advance machinery carries it forward in O(D))."""
        if not (
            self.config.lookup_prewarm
            and snap.num_edges >= LOOKUP_PREWARM_MIN_EDGES
            and getattr(snap, "_lookup_index", None) is None
            and not self.__dict__.get("_prewarm_inflight")
        ):
            return
        import threading

        from .lookup import lookup_index

        self._prewarm_inflight = True

        def run():
            try:
                lookup_index(snap, mark_used=False)
            finally:
                self._prewarm_inflight = False

        threading.Thread(
            target=run, name="gochugaru-lookup-prewarm", daemon=True
        ).start()

    def _delta_prev_ok(self, prev: DeviceSnapshot) -> bool:
        """Layout eligibility of ``prev`` for the incremental prepare —
        the sharded engine overrides (its base tables are bucket-sharded)."""
        return prev.flat_meta is not None and not prev.flat_meta.sharded

    def _place_replicated(self, v: np.ndarray):
        """Ship a replicated (non-bucket-sharded) host array — overlays,
        node types, stored-context tables.  The sharded engine overrides
        with an explicitly-replicated device_put."""
        return jnp.asarray(v)

    def _prepare_delta(
        self, snap: Snapshot, prev: DeviceSnapshot
    ) -> Optional[DeviceSnapshot]:
        """The incremental prepare, or None → caller does a full one.

        The produced DeviceSnapshot REUSES prev's device buffers for every
        base table (no re-ship); only the delta overlays, a possibly-grown
        node_type column, and re-encoded stored-context tables move.  The
        legacy (non-flat) kernel columns inside are left at the BASE
        revision — a delta-prepared snapshot serves the flat path, and the
        engine's check paths only fall back to the legacy kernel when
        flat_meta is None, which is never the case here.  Shared verbatim
        by the sharded engine (whose overlay placement is replicated
        across the mesh) through the two hooks above."""
        if not (
            self.config.use_flat
            and self.config.flat_blockslice
            and self._delta_prev_ok(prev)
        ):
            return None
        from dataclasses import replace as _dc_replace

        from .flat import build_delta_arrays

        built = build_delta_arrays(snap, prev, self.compiled, self.config)
        if built is None:
            return None
        dl_arrays, dmeta, acc, extras = built
        arrays = dict(prev.arrays)
        # drop the previous overlay's tables: the new overlay replaces them
        # (a shrunk accumulated delta must not leave stale tables behind)
        for k in [k for k in arrays if k.startswith("dl_")]:
            del arrays[k]
        strings = prev.strings
        if len(snap.contexts) != len(prev.snapshot.contexts):
            ectx, strings = self._ectx_tables(snap)
            old = prev.arrays.get("ectx_vi")
            if old is not None and ectx["ectx_vi"].shape[0] != old.shape[0]:
                return None  # context bucket grew: shapes change, rebuild
            arrays.update(
                {k: self._place_replicated(v) for k, v in ectx.items()}
            )
        if snap.num_nodes > prev.snapshot.num_nodes:
            NN = int(prev.arrays["node_type"].shape[0])
            if snap.num_nodes > NN:
                return None  # node bucket outgrown: every node shape moves
            nt = _pad_payload(snap.node_type, NN, -1)
            prev_dt = prev.arrays["node_type"].dtype
            if prev_dt != nt.dtype:
                # the base narrowed node_type (HBM-lean); fresh interner
                # type ids past the narrow dtype's range would WRAP —
                # bail to a full prepare, which re-derives the width
                if int(nt.max(initial=0)) > np.iinfo(prev_dt).max:
                    return None
                nt = nt.astype(prev_dt)
            arrays["node_type"] = self._place_replicated(nt)
        arrays.update(
            {k: self._place_replicated(v) for k, v in dl_arrays.items()}
        )
        for k in extras.get("drop_keys", ()):
            arrays.pop(k, None)  # despec'd packed-offset anchors
        # an empty collapsed delta (or one that cancelled out) compiles as
        # the plain base kernel — don't pay a retrace for DeltaMeta()
        meta = _dc_replace(
            prev.flat_meta, delta=dmeta if dl_arrays else None,
            **extras.get("meta_up", {}),
        )
        self.record_device_bytes(arrays)
        if meta.delta is not None:
            # an LSM delta level declines the device frontier
            # (engine/spmv.py frontier_ok), so lookups on this chain
            # walker-serve: start the transposed-index build in the
            # background NOW instead of paying it inside the first
            # post-delta lookup (one in-flight build per engine; the
            # chain-advance machinery carries it forward afterwards)
            self._maybe_prewarm_walker_index(snap)
        return DeviceSnapshot(
            revision=snap.revision,
            arrays=arrays,
            tid_map=prev.tid_map,
            snapshot=snap,
            strings=strings,
            flat_meta=meta,
            delta_acc=acc,
            fold_state=prev.fold_state,
            closure_state=extras.get("closure_state"),
            host_arrays=prev.host_arrays,
        )

    # -- query lowering --------------------------------------------------
    def _lower_queries(
        self, snap: Snapshot, rels: Sequence[Relationship],
        strings: Optional[Dict[str, int]] = None,
        span=_trace.NOOP,
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
        """Host lowering of one batch, Relationship objects to interned
        int32 query columns and the request-context tables, as one
        ``engine.lower`` stage (wall, and thread CPU while it records).
        Column operations only: nothing but the pulls of the six fields
        (on a schema with caveats, of the request context and of each
        parameter it names) runs once per row.  On a ``NativeInterner``
        with ``native/lower.cpp`` loaded the six pulls, the id packing,
        the type ids and the two slot columns are one native pass
        (``native.lower.pull``; ``engine.lower_native_batches`` counts the
        batch, the stage notes ``native=True``); otherwise the Python
        pass, which gives the same columns.  The batch's node ids come
        from one locked interner call (``lookup_packed`` after the native
        pull, else ``lookup_pairs``): ``engine.intern_s`` is observed
        around it, ``intern.lookups`` counts its 2·B keys and
        ``intern.batch_calls`` the call.  On a schema with caveats the
        request-context dedup and encode are ``engine.context_s``, and
        ``engine.context_batches`` / ``engine.query_contexts`` /
        ``engine.context_checks`` count the batch, its distinct request
        contexts and its checks that carry one;
        ``engine.context_keyed_columns`` / ``engine.context_repr_columns``
        the parameter columns ``caveats.device.dedup_contexts`` keyed by
        value / by ``repr``."""
        with _trace.stage("engine.lower", span, cpu=True) as st:
            return self._lower(snap, rels, strings, st)

    def _lower(self, snap, rels, strings, st):
        B = len(rels)
        interner = snap.interner
        slot_of = self.compiled.slot_of_name
        # an empty subject relation is the direct subject, -1; a non-empty
        # one with no slot reads -2 until the rows are forced false below
        srel_of = {**slot_of, "": -1}
        wc_of = snap.wildcard_node_of_type

        pulled = (_native_lower.pull(rels, interner, slot_of, srel_of)
                  if isinstance(interner, NativeInterner) else None)
        # every node id of the batch in ONE interner call (2·B keys)
        if pulled is not None:
            buf, offsets, type_ids, q_perm, q_srel = pulled
            t0 = _time.perf_counter()
            nodes = interner.lookup_packed(buf, offsets, type_ids)
            intern_s = _time.perf_counter() - t0
        else:
            res_type = [r.resource_type for r in rels]
            res_id = [r.resource_id for r in rels]
            res_rel = [r.resource_relation for r in rels]
            subj_type = [r.subject_type for r in rels]
            subj_id = [r.subject_id for r in rels]
            subj_rel = [r.subject_relation for r in rels]
            t0 = _time.perf_counter()
            nodes, type_ids = interner.lookup_pairs(
                res_type + subj_type, res_id + subj_id)
            intern_s = _time.perf_counter() - t0
            q_perm = np.fromiter(
                map(slot_of.get, res_rel, repeat(-1)), np.int32, B)
            q_srel = np.fromiter(
                map(srel_of.get, subj_rel, repeat(-2)), np.int32, B)
        q_res, q_subj = nodes[:B], nodes[B:]
        # the wildcard node of the subject's type, unless the subject is
        # that node: wc_of holds lookup(type, "*"), so q_subj equals it
        # exactly where subj_id is the wildcard id
        stid = type_ids[B:]
        typed = (stid >= 0) & (stid < wc_of.shape[0])
        wc = wc_of[np.where(typed, stid, 0)]
        q_wc = np.where(typed & (wc != q_subj), wc, np.int32(-1))

        no_slot = q_srel == -2
        # reflexive userset identity: the same (type, id) on both sides and
        # the same non-empty relation.  A name has one slot and a known
        # node one (type, id), so equal known slots and equal known nodes
        # prove it; where the relation has no slot or the nodes are both
        # unknown (-1 == -1 proves nothing) the row's strings decide
        same = (q_res == q_subj) & (
            ((q_srel >= 0) & (q_srel == q_perm)) | (no_slot & (q_perm < 0)))
        q_self = same & (q_srel >= 0) & (q_subj >= 0)
        for i in np.flatnonzero(same & ~q_self).tolist():
            r = rels[i]
            q_self[i] = (
                r.resource_type == r.subject_type
                and r.resource_id == r.subject_id
                and r.resource_relation == r.subject_relation
            )
        # an unknown subject relation can never be granted; -1 would alias
        # "direct subject", so force the query false
        q_res[no_slot] = -1
        q_srel[no_slot] = -1

        # dedup request contexts (the caveat_context of the query
        # relationship IS the request context, client/client.go:241-259)
        # in the native pass where the pull ran and it takes the batch,
        # else a parameter column at a time, and encode them:
        # ``engine.context_s``, a batch, on a schema with caveats.  A check
        # with an empty context keeps -1; any other gets a row, even one
        # that names no parameter
        t0 = _time.perf_counter()
        q_ctx = np.full(B, -1, np.int32)
        ctx_rows: List[Mapping] = []
        keyed = by_repr = 0
        grouped = None
        if self.caveat_plan is not None and pulled is not None:
            grouped = _native_lower.contexts(
                rels, self.caveat_plan.slots_of_param)
        if grouped is not None:
            q_ctx, ctx_rows, keyed = grouped
        elif self.caveat_plan is not None:
            contexts = [r.caveat_context for r in rels]
            at = np.flatnonzero(np.fromiter(map(bool, contexts), bool, B))
            if at.size:
                if at.size < B:
                    contexts = [contexts[i] for i in at.tolist()]
                q_ctx[at], ctx_rows, keyed, by_repr = dedup_contexts(
                    self.caveat_plan, contexts)
        qctx = self._encode_query_contexts(ctx_rows, strings)
        context_s = _time.perf_counter() - t0

        m = metrics.default
        m.inc("intern.lookups", 2 * B)
        m.inc("intern.batch_calls")
        m.observe("engine.intern_s", intern_s)
        if pulled is not None:
            m.inc("engine.lower_native_batches")
        st.note(batch=B, intern_s=round(intern_s, 6),
                native=pulled is not None)
        if self.caveat_plan is not None:
            m.observe("engine.context_s", context_s)
            m.inc("engine.context_batches")
            m.inc("engine.query_contexts", len(ctx_rows))
            m.inc("engine.context_checks", int(np.count_nonzero(q_ctx >= 0)))
            m.inc("engine.context_keyed_columns", keyed)
            m.inc("engine.context_repr_columns", by_repr)
            if grouped is not None:
                m.inc("engine.context_native_batches")
            st.note(context_s=round(context_s, 6), contexts=len(ctx_rows),
                    native_ctx=grouped is not None)
        queries = {
            "q_res": q_res, "q_perm": q_perm, "q_subj": q_subj,
            "q_srel": q_srel, "q_wc": q_wc, "q_ctx": q_ctx,
            "q_self": q_self,
        }
        return queries, qctx

    def _two_phase_call(
        self, dsnap: DeviceSnapshot, queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray], now_us: Optional[int], BP: int, span,
    ):
        """Enqueue the two-phase ``_fn`` program over one batch padded to
        ``BP``, as an ``engine.enqueue`` stage: the one reader of
        ``subject_rows``, built here and padded to its own pow2 bucket
        with -1 rows.  Counted in ``engine.subject_rows``, once a batch
        — no flat dispatch comes here.  Returns the padded device
        planes."""
        with _trace.stage("engine.enqueue", span) as st:
            st.note(legacy=True)
            metrics.default.inc("engine.subject_rows")
            uniq, q_row = subject_rows(
                queries["q_subj"], queries["q_srel"], queries["q_wc"],
                queries["q_ctx"],
            )
            U = uniq.shape[0]
            u = np.full(
                (_ceil_pow2(U, self.config.batch_bucket_min), 4), -1, np.int32)
            u[:U] = uniq
            B = q_row.shape[0]

            def padq(a, fill):
                out = np.full(BP, fill, a.dtype)
                out[:B] = a
                return jnp.asarray(out)

            return self._fn(
                self._legacy_arrays(dsnap), dsnap.tid_map,
                jnp.int32(dsnap.snapshot.now_rel32(now_us)),
                jnp.asarray(u[:, 0]), jnp.asarray(u[:, 1]), jnp.asarray(u[:, 2]),
                jnp.asarray(u[:, 3]),
                padq(queries["q_res"], -1), padq(queries["q_perm"], -1),
                padq(queries["q_subj"], -1), padq(queries["q_srel"], -1),
                padq(queries["q_wc"], -1), padq(q_row, 0),
                padq(queries["q_self"], False), padq(queries["q_ctx"], -1),
                self._qctx_device(qctx),
            )

    def _encode_query_contexts(
        self, ctx_rows: List[Mapping], strings: Optional[Dict[str, int]]
    ) -> Dict[str, np.ndarray]:
        """Encode deduped request contexts into padded qctx tables.  The
        context-free case (most checks) returns a per-engine singleton so
        dispatch paths can cache its device form — 4 of the ~12 small
        host→device puts a small-batch check pays."""
        if not ctx_rows and self._empty_qctx_np is not None:
            return self._empty_qctx_np
        if self.caveat_plan is None:
            P = 1
            out = {
                "vi": np.zeros((1, P), np.int32),
                "vf": np.zeros((1, P), np.float32),
                "pr": np.zeros((1, P), bool),
                "host": np.zeros((1, 1), bool),
            }
            if not ctx_rows:
                self._empty_qctx_np = out
            return out
        table = encode_contexts(
            self.caveat_plan, ctx_rows,
            strings if strings is not None else dict(self.caveat_plan.base_strings),
            extra_strings={},
        )
        NQ = _ceil_pow2(table.vi.shape[0], 1)

        def padrows(a: np.ndarray) -> np.ndarray:
            out = np.zeros((NQ,) + a.shape[1:], a.dtype)
            out[: a.shape[0]] = a
            return out

        out = {
            "vi": padrows(table.vi),
            "vf": padrows(table.vf),
            "pr": padrows(table.present),
            "host": padrows(table.host),
        }
        if not ctx_rows:
            self._empty_qctx_np = out
        return out

    def _qctx_device(self, qctx: Dict[str, np.ndarray]):
        """Device form of the qctx tables, cached for the context-free
        singleton (checks without request context skip 4 host→device
        transfers per dispatch)."""
        if qctx is self._empty_qctx_np:
            if self._empty_qctx_jnp is None:
                self._empty_qctx_jnp = {
                    k: jnp.asarray(v) for k, v in qctx.items()
                }
            return self._empty_qctx_jnp
        return {k: jnp.asarray(v) for k, v in qctx.items()}

    # -- latency-mode path (engine/latency.py) ---------------------------
    #: bound on engine-wide pinned latency executables (FIFO, same
    #: rationale as FLAT_FN_CACHE_MAX; each pin is one compiled XLA
    #: program at one small-batch tier)
    LATENCY_PIN_CACHE_MAX = 32

    def latency_path(self, dsnap: DeviceSnapshot):
        """The warm small-batch dispatcher attached to this prepared
        snapshot (created on first use; see engine/latency.py)."""
        if dsnap.latency_path is None:
            from .latency import LatencyPath

            with self._latency_pins_lock:
                if dsnap.latency_path is None:
                    dsnap.latency_path = LatencyPath(self, dsnap)
        return dsnap.latency_path

    #: bounded retries for the deadline-less engine-level latency entry
    #: point (callers with a Context pass their own; the envelope itself
    #: is the client's, utils/retry.py)
    LATENCY_RETRY_TRIES = 3

    def check_columns_latency(
        self,
        dsnap: DeviceSnapshot,
        q_res: np.ndarray,
        q_perm: np.ndarray,
        q_subj: np.ndarray,
        *,
        q_srel: Optional[np.ndarray] = None,
        q_wc: Optional[np.ndarray] = None,
        q_ctx: Optional[np.ndarray] = None,
        qctx_rows: Optional[Sequence[Mapping[str, Any]]] = None,
        now_us: Optional[int] = None,
        ctx: Optional[Any] = None,
    ):
        """Latency-mode bulk check from pre-interned columns: pinned
        kernel, tiered padding, per-stage budget metrics.  Falls back to
        ``check_columns`` when the latency path cannot serve the batch
        (no flat tables, too many distinct permissions, batch beyond the
        top tier) — same result contract either way.

        Failure contract now matches the batch path (client.py check):
        raw dispatch errors are classified onto the retry taxonomy
        (transient → ``UnavailableError``) and transient failures retry
        under the reference's backoff envelope — bounded by ``ctx`` when
        given, else by ``LATENCY_RETRY_TRIES`` so a deadline-less bench
        caller cannot hang on a persistent fault."""

        span = _trace.span_of(ctx) if ctx is not None else _trace.NOOP

        def dispatch():
            try:
                out = self.latency_path(dsnap).dispatch_columns(
                    q_res, q_perm, q_subj, q_srel=q_srel, q_wc=q_wc,
                    q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=now_us,
                    span=span,
                )
                if out is not None:
                    return out
                return self.check_columns(
                    dsnap, q_res, q_perm, q_subj, q_srel=q_srel, q_wc=q_wc,
                    q_ctx=q_ctx, qctx_rows=qctx_rows, now_us=now_us,
                )
            except Exception as e:
                classified = classify_dispatch_exception(e)
                if classified is None or classified is e:
                    raise
                raise classified

        return retry_retriable_errors(
            ctx if ctx is not None else _background(),
            dispatch,
            max_tries=None if ctx is not None else self.LATENCY_RETRY_TRIES,
        )

    # -- flat-kernel plumbing (engine/flat.py) ---------------------------
    #: bound on cached per-permission-subset kernels (simple FIFO eviction:
    #: a pathological workload cycling through C(P, ≤8) subsets pays
    #: recompiles but can't grow device/host memory without bound)
    FLAT_FN_CACHE_MAX = 16

    def _legacy_arrays(self, dsnap: DeviceSnapshot) -> Dict[str, Any]:
        """Argument dict for the legacy (non-flat) kernel.  HBM-lean
        snapshots keep the raw O(E) columns host-side; the first legacy
        fallback ships them once and caches the merged dict on the
        snapshot."""
        if dsnap.host_arrays is None:
            return dsnap.arrays
        if dsnap.legacy_cache is None:
            merged = dict(dsnap.arrays)
            merged.update(jax.device_put(dsnap.host_arrays))
            dsnap.legacy_cache = merged
        return dsnap.legacy_cache

    def _flat_fn_for(self, slots: Tuple[int, ...], meta, witness: bool = False):
        key = (slots, meta) if not witness else (slots, meta, "wit")
        fn = self._flat_fns.get(key)
        if fn is None:
            from .flat import make_flat_fn

            fn = make_flat_fn(
                self.compiled, self.plan, self.config, meta, slots,
                caveat_plan=self.caveat_plan, witness=witness,
            )
            while len(self._flat_fns) >= self.FLAT_FN_CACHE_MAX:
                self._flat_fns.pop(next(iter(self._flat_fns)))
            self._flat_fns[key] = fn
        return fn

    def flat_fn_and_args(
        self,
        dsnap: DeviceSnapshot,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        now,
        B: int,
        jit: bool = True,
        bucket_min: int = 0,
        witness: bool = False,
    ):
        """The flat kernel + its lowered padded argument tuple — the ONE
        place that knows the kernel's signature (check paths, bench.py,
        __graft_entry__ and the witness extraction all call this).  None
        when the flat path is unavailable (disabled, unpackable graph, or
        more distinct permissions in the batch than flat_max_slots).
        ``witness=True`` selects the armed kernel (same signature, extra
        witness-plane output) — cached separately, never registered in
        the device cost ledger (the ledger key names the serving
        kernel)."""
        if dsnap.flat_meta is None:
            return None
        slots = tuple(
            sorted({int(s) for s in np.unique(queries["q_perm"]) if s >= 0})
        )
        if len(slots) > self.config.flat_max_slots:
            return None
        from .flat import build_qm

        if jit:
            fn = self._flat_fn_for(slots, dsnap.flat_meta, witness=witness)
        else:
            from .flat import make_flat_fn

            fn = make_flat_fn(
                self.compiled, self.plan, self.config, dsnap.flat_meta,
                slots, caveat_plan=self.caveat_plan, jit=False,
                witness=witness,
            )
        BP = _ceil_pow2(B, max(bucket_min, self.config.batch_bucket_min))
        # ONE packed query matrix (flat.QM_LAYOUT) → one device transfer
        args = (
            dsnap.arrays, dsnap.tid_map, now,
            jnp.asarray(build_qm(queries, BP, dsnap.flat_meta)),
            self._qctx_device(qctx),
        )
        if jit and not witness:
            # device cost ledger: the batch-path program registers a
            # LAZY capture over ShapeDtypeStruct avals (no device
            # buffers pinned, no compile here) — realized only when a
            # consumer explicitly asks (/perf?compile=1, perf smoke).
            # The engine-local registered-set keeps the steady-state
            # dispatch path to one set lookup (no global ledger lock,
            # no key formatting per call — same discipline as
            # spmv.FrontierKernels._register_cost)
            rk = (slots, BP, dsnap.flat_meta)
            if rk not in self._perf_cost_reg:
                self._perf_cost_reg.add(rk)
                ck = (
                    f"slots={slots};B={BP};"
                    f"meta={hash(dsnap.flat_meta) & 0xFFFFFFFF:08x}"
                )
                _perf.register_cost_thunk(
                    "batch", ck,
                    lambda fn=fn, avals=_perf.avals_of(args): fn.lower(
                        *avals
                    ).compile(),
                )
        return fn, args

    def _flat_call(
        self,
        dsnap: DeviceSnapshot,
        queries: Dict[str, np.ndarray],
        qctx: Dict[str, np.ndarray],
        now,
        B: int,
        bucket_min: int = 0,
    ):
        """Dispatch the flat kernel; returns padded device (d, p, ovf), or
        None when the flat path is unavailable."""
        got = self.flat_fn_and_args(
            dsnap, queries, qctx, now, B, bucket_min=bucket_min
        )
        if got is None:
            return None
        fn, args = got
        return fn(*args)

    # -- decision provenance (engine/explain.py) -------------------------
    def witness_codes(
        self,
        dsnap: DeviceSnapshot,
        rels: Sequence[Relationship],
        *,
        now_us: Optional[int] = None,
    ) -> Optional[np.ndarray]:
        """Per-check device WITNESS codes for a batch: the winning-branch
        plane the armed flat kernel emits (engine/flat.py
        ``make_flat_fn(witness=True)``; codes in engine/explain.py).
        Nonzero only for device-definite allowed verdicts — conditional/
        overflow rows (host-oracle resolved) report 0, and rows the flat
        path cannot serve at all return None (the explain walk then runs
        unseeded).  Armed kernels cache separately from the serving
        kernels, so calling this never perturbs the disarmed fast path."""
        meta = dsnap.flat_meta
        if meta is None or meta.sharded:
            return None
        snap = dsnap.snapshot
        queries, qctx = self._lower_queries(snap, rels, dsnap.strings)
        B = len(rels)
        got = self.flat_fn_and_args(
            dsnap, queries, qctx, jnp.int32(snap.now_rel32(now_us)), B,
            witness=True,
        )
        if got is None:
            return None
        fn, args = got
        d, p, ovf, wit = jax.device_get(fn(*args))
        wit = wit[:B].copy()
        # host-resolved rows (conditional, overflow) carry no trusted
        # device witness — the oracle walk explains them unseeded
        wit[(p[:B] & ~d[:B]) | ovf[:B]] = 0
        return wit

    # -- the batched check ----------------------------------------------
    def check_batch(
        self,
        dsnap: DeviceSnapshot,
        rels: Sequence[Relationship],
        *,
        now_us: Optional[int] = None,
        latency: bool = False,
        span=_trace.NOOP,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (definite, possible, overflow) bool arrays of len(rels).

        ``definite`` → permission granted.  ``possible & ~definite`` →
        conditional on caveats the device didn't evaluate; the caller
        resolves via the host oracle.  ``overflow`` → a static cap was
        exceeded; the caller must re-check on the host.

        With ``latency``, small batches route through the latency-mode
        path (engine/latency.py: pinned kernel at a fixed tier, staged
        budget metrics); batches it cannot serve fall through to the
        ordinary dispatch below, same contract.  ``span`` is the
        request's trace span (utils/trace.py): sampled dispatches record
        a ``device.check_batch`` child whose children are the stages
        ``engine.lower`` / ``.enqueue`` / ``.fetch``; the NOOP span costs
        one branch.  A batch whose programs read expiries
        (``FlatMeta.gates_expiry``) counts ``engine.expiry_batches``, and
        ``engine.fold_until_row_batches`` too where they read the fold's
        until slices as key + until rows (``FlatMeta.fold_until_rows``);
        one whose programs read a folded operand of a permission that
        holds ``-`` or ``&`` counts ``engine.excl_fold_batches``
        (``FlatMeta.reads_hidden``)."""
        if not rels:
            z = np.zeros(0, bool)
            return z, z, z
        faults.fire("device.dispatch")
        t_lower = _time.perf_counter()
        dsp = span.child("device.check_batch", t=t_lower, batch=len(rels))
        try:
            snap = dsnap.snapshot
            queries, qctx = self._lower_queries(
                snap, rels, dsnap.strings, span=dsp
            )
            B = len(rels)
            fm = dsnap.flat_meta
            if fm is not None and fm.gates_expiry:
                metrics.default.inc("engine.expiry_batches")
                if fm.fold_until_rows:
                    metrics.default.inc("engine.fold_until_row_batches")
            if fm is not None and fm.reads_hidden(
                self.plan, queries["q_perm"]
            ):
                metrics.default.inc("engine.excl_fold_batches")
            if latency:
                out = self.latency_path(dsnap).dispatch(
                    queries, qctx, B, snap.now_rel32(now_us),
                    t_start=t_lower, span=dsp,
                )
                if out is not None:
                    return out
            now_flat = jnp.int32(snap.now_rel32(now_us))
            PB = self.config.pipeline_batch()
            if PB and B > PB and dsnap.flat_meta is not None:
                # sub-batch pipeline: dispatch every chunk before fetching
                # any (the async queue overlaps lowering with compute); one
                # shared compiled program per PB bucket
                subs = []
                with _trace.stage("engine.enqueue", dsp):
                    for lo in range(0, B, PB):
                        sub = {k: v[lo:lo + PB] for k, v in queries.items()}
                        o = self._flat_call(
                            dsnap, sub, qctx, now_flat, min(PB, B - lo),
                            bucket_min=PB,
                        )
                        if o is None:
                            subs = None
                            break
                        subs.append((min(PB, B - lo), o))
                if subs is not None:
                    metrics.default.inc("engine.sub_batches", len(subs))
                    with _trace.stage("engine.fetch", dsp):
                        ds, ps, os_ = [], [], []
                        for n, o in subs:
                            d, p, ovf = jax.device_get(o)
                            ds.append(d[:n]); ps.append(p[:n]); os_.append(ovf[:n])
                        return (
                            np.concatenate(ds), np.concatenate(ps),
                            np.concatenate(os_),
                        )
            with _trace.stage("engine.enqueue", dsp):
                out = self._flat_call(dsnap, queries, qctx, now_flat, B)
            if out is not None:
                with _trace.stage("engine.fetch", dsp):
                    d, p, ovf = jax.device_get(out)
                return d[:B], p[:B], ovf[:B]
            d, p, ovf = self._two_phase_call(
                dsnap, queries, qctx, now_us,
                _ceil_pow2(B, self.config.batch_bucket_min), dsp,
            )
            # one device→host fetch for all three planes: separate np.asarray
            # calls round-trip the dispatch boundary once each, which dominates
            # small-batch latency on remote-attached TPUs
            with _trace.stage("engine.fetch", dsp):
                d, p, ovf = jax.device_get((d, p, ovf))
            return d[:B], p[:B], ovf[:B]
        finally:
            dsp.end()

    # -- columnar bulk check ---------------------------------------------
    def _columns_preamble(
        self,
        dsnap: DeviceSnapshot,
        q_res: np.ndarray,
        q_perm: np.ndarray,
        q_subj: np.ndarray,
        q_srel: Optional[np.ndarray],
        q_wc: Optional[np.ndarray],
        q_ctx: Optional[np.ndarray],
        qctx_rows,
        span=_trace.NOOP,
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
        """Shared columnar-check preamble: optional-column defaulting,
        query-context encoding, and the reflexive-self derivation — one
        definition so the single-chip and sharded paths cannot drift.
        The columnar path's ``engine.lower`` stage."""
        with _trace.stage("engine.lower", span, cpu=True):
            return self._preamble(
                dsnap, q_res, q_perm, q_subj, q_srel, q_wc, q_ctx, qctx_rows
            )

    def _preamble(
        self, dsnap, q_res, q_perm, q_subj, q_srel, q_wc, q_ctx, qctx_rows
    ):
        B = q_res.shape[0]
        if q_srel is None:
            q_srel = np.full(B, -1, np.int32)
        if q_wc is None:
            q_wc = np.full(B, -1, np.int32)
        if q_ctx is None:
            q_ctx = np.full(B, -1, np.int32)
        qctx = self._encode_query_contexts(list(qctx_rows or []), dsnap.strings)
        queries = {
            "q_res": np.ascontiguousarray(q_res, np.int32),
            "q_perm": np.ascontiguousarray(q_perm, np.int32),
            "q_subj": np.ascontiguousarray(q_subj, np.int32),
            "q_srel": np.ascontiguousarray(q_srel, np.int32),
            "q_wc": np.ascontiguousarray(q_wc, np.int32),
            "q_ctx": np.ascontiguousarray(q_ctx, np.int32),
            # reflexive userset identity (a userset is a member of itself),
            # same semantics as _lower_queries' q_self: slots are shared
            # between q_perm and q_srel, and equal interned nodes mean
            # equal (type, id)
            "q_self": (q_res == q_subj) & (q_srel >= 0) & (q_perm == q_srel),
        }
        return queries, qctx

    def check_columns_pipelined(
        self,
        dsnap: DeviceSnapshot,
        q_res: np.ndarray,
        q_perm: np.ndarray,
        q_subj: np.ndarray,
        *,
        q_ctx: Optional[np.ndarray] = None,
        qctx_rows: Optional[Sequence[Mapping[str, Any]]] = None,
        now_us: Optional[int] = None,
        sub_batch: Optional[int] = None,
    ):
        """Pipelined bulk check over pre-interned columns: the batch is
        split into ``sub_batch``-sized dispatches enqueued back-to-back
        (jax async dispatch), then fetched IN ORDER as they complete —
        yields ``(lo, hi, d, p, ovf)`` per sub-batch, so a consumer sees
        the first results after one sub-batch latency instead of the
        whole batch's (BASELINE config-4 tail; the serving analogue of
        the reference's chunked CheckIter, client/client.go:164-180)."""
        PB = sub_batch or self.config.pipeline_batch() or q_res.shape[0]
        B = q_res.shape[0]
        outs = []
        for lo in range(0, B, PB):
            hi = min(lo + PB, B)
            outs.append((lo, hi, self.check_columns(
                dsnap, q_res[lo:hi], q_perm[lo:hi], q_subj[lo:hi],
                q_ctx=None if q_ctx is None else q_ctx[lo:hi],
                qctx_rows=qctx_rows, now_us=now_us,
                fetch=False, bucket_min=PB,
            )))
        for lo, hi, out in outs:
            with _trace.stage("engine.fetch"):
                d, p, ovf = jax.device_get(out)
            n = hi - lo
            yield lo, hi, d[:n], p[:n], ovf[:n]

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
        qctx_rows: Optional[Sequence[Mapping[str, Any]]] = None,
        now_us: Optional[int] = None,
        fetch: bool = True,
        bucket_min: int = 0,
        span=_trace.NOOP,
    ):
        """Bulk check straight from pre-interned int32 columns — the fast
        path for 100k+-item batches, where per-item Relationship objects
        would dominate (the analogue of the reference's chunked iterator
        APIs, client/client.go:164-180).  ``bucket_min`` raises the pow2
        padding floor — callers with highly variable batch sizes (device
        lookups) use a coarse floor so warm calls share one compiled
        program instead of retracing per fresh bucket.

        With ``fetch`` (default) returns (definite, possible, overflow)
        numpy arrays trimmed to the batch length, fetched in ONE
        device→host transfer.  With ``fetch=False`` returns the raw padded
        device outputs (length = pow2 bucket ≥ B) for pipelined dispatch
        loops; fetch them with ``jax.device_get`` on the full arrays —
        materializing *sliced* views of jit outputs degrades every
        subsequent dispatch on remote-attached platforms.
        """
        faults.fire("device.dispatch")
        snap = dsnap.snapshot
        B = q_res.shape[0]
        BP = _ceil_pow2(B, max(bucket_min, self.config.batch_bucket_min))
        queries, qctx = self._columns_preamble(
            dsnap, q_res, q_perm, q_subj, q_srel, q_wc, q_ctx, qctx_rows,
            span=span,
        )
        with _trace.stage("engine.enqueue", span):
            now_flat = jnp.int32(snap.now_rel32(now_us))
            out = self._flat_call(
                dsnap, queries, qctx, now_flat, B, bucket_min=bucket_min
            )
        if out is not None:
            if not fetch:
                return out
            with _trace.stage("engine.fetch", span):
                d, p, ovf = jax.device_get(out)
            return d[:B], p[:B], ovf[:B]
        d, p, ovf = self._two_phase_call(dsnap, queries, qctx, now_us, BP, span)
        if not fetch:
            return d, p, ovf
        with _trace.stage("engine.fetch", span):
            d, p, ovf = jax.device_get((d, p, ovf))
        return d[:B], p[:B], ovf[:B]
