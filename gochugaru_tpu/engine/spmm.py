"""One masked-SpMM sparse core: multi-hop lookups, checks, and the fold
T-join as instances of a single batched semiring primitive.

The engine grew three hand-built kernel families — the forward check
probes (engine/flat.py), the reverse frontier SpMV (engine/spmv.py),
and the factored fold T-join (engine/fold.py) — that are all the same
computation: a masked sparse matrix product over the relation graph,

    C = M .* (A ⊕.⊗ B)

with the semiring multiply ⊗ = the packed caveat/expiry gate (an edge
contributes only while live and unconditionally resolvable — the same
``decode_block`` filter the Check kernel fuses into its gathers), the
add ⊕ = short-circuited max/OR (a grant is a grant; until-values reduce
by max), and the mask M = the seen-set bitmaps plus the schema-level
type-safety pruning tables (RedisGraph runs a whole graph database on
exactly this GraphBLAS reduction, arXiv:1905.01294; Graphulo benchmarks
the server-side kernels at database scale, arXiv:1609.08642).

This module makes the primitive explicit and re-expresses the families
on it:

- **Fused multi-hop lookups** (the tentpole): LookupResources /
  LookupSubjects run their WHOLE frontier fixpoint — up to
  ``spmm_rounds`` hops — in ONE pinned device dispatch.  The frontier
  is carried on-device between hops at a fixed pow2 capacity, dedup is
  on-device uint32 bitmaps (the ⊕ short-circuit: a key contributes
  once), and each hop reuses the spmv probe/emission bodies verbatim —
  one hop IS one masked SpMV, the K-hop program is the SpMM.  The host
  only seeds, paginates, and resolves cursors.  This removes the
  per-hop dispatch floor bench8 measures as 0.04M mixed-user
  candidates/s against 1.50M/s bulk: a ~1k-resource answer pays ONE
  dispatch instead of 2·hops.
- **Overflow honesty**: every fixed capacity (frontier width, per-round
  emission, candidate buffer, round budget) has an on-device overflow
  flag; an overflowing query falls back to the looped spmv path — which
  is also the streaming path bulk answers want — so the fused program
  trades dispatch count for coverage, never correctness.
- **The fold T-join** (``tjoin_spmm``): the userset⋈closure join that
  builds flat.py's T-index is the HOST instance of the same primitive
  over the (min, max) until-semiring — ⊗ intersects validity windows,
  ⊕ keeps the widest — produced by a generic sorted-operand product
  instead of a bespoke kernel.
- **Checks**: the flat probe kernel is the 1-hop degenerate instance
  (frontier = the query batch, one masked gather+gate per probe site);
  it already shares the packed gate decode and, through
  engine/latency.py, the (snapshot, meta, tier) pinned-executable
  discipline this module's fused programs follow.

Parity: ``EngineConfig.spmm`` (default on) is the
``flat_packed=False``-style lever — off reproduces the looped spmv path
and the bespoke ``t_join_core`` byte-for-byte; the fused answers are
asserted bitwise-equal to both the legacy paths and the host walker
(tests/test_spmm.py).  Sharded snapshots keep the owner-routed looped
hop path (parallel/sharded.py ``lookup_hops_for``) — routing happens
per hop batch there, and the fused single-chip program must not change
that contract.

Counters: ``spmm.dispatches`` (fused program launches — a ≥2-hop
lookup answers with exactly ONE), ``spmm.fallbacks`` (overflows to the
looped path), ``spmm.rounds`` (+ the fixpoint rounds a fused dispatch
ran: its loop counter, fetched with the answer), and the
``spmm.dispatch`` fault site (utils/faults.py)
fire under the client's retry envelope exactly like ``lookup.dispatch``.
Fused programs register with the PR-12 cost ledger (utils/perf.py,
kind ``spmm``) so ``/perf`` and the roofline columns attribute their
gathered bytes.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..utils import faults, metrics
from ..utils import trace as _trace
from .hash import _ceil_pow2

_mt = metrics.default

#: host-side pad widths of the fused programs' seed arguments (static,
#: so every query of a geometry shares ONE compiled program)
_SEED_KEYS = 4
_SEED_NODES = 2

#: int32 sentinel marking dead lanes in on-device pools (sorts last)
_SENT = (1 << 31) - 1


# ---------------------------------------------------------------------------
# the host instance: the fold T-join as a sorted-operand semiring product
# ---------------------------------------------------------------------------


def masked_semiring_spmm(
    a_i: np.ndarray, a_k: np.ndarray, a_v: np.ndarray,
    b_k: np.ndarray, b_j: np.ndarray, b_planes: Tuple[np.ndarray, ...],
    cap_rows: int,
) -> Optional[Tuple[np.ndarray, ...]]:
    """C = (A ⊕.⊗ B) + A⊗I over sorted sparse operands on the host:
    A's rows are (i, k, v), B's are (k, j, plane-values); ⊗ =
    ``np.minimum`` (until-window intersection), ⊕ = per-(i, j) max
    (the widest surviving window wins), and the identity term keeps A's
    own (i, k) rows riding along (the direct group entries of the
    T-index).  The mask is the size gate: the product is sized with two
    searchsorted passes BEFORE materializing, and ``None`` past
    ``cap_rows`` declines (a popular k with a huge B in-degree must
    disable the index, not OOM).  Returns (C_i, C_j, *plane-maxima)."""
    from ..store.closure import _expand_join

    order = np.argsort(b_k, kind="stable")
    b_sorted = b_k[order]
    join_rows = int(
        (
            np.searchsorted(b_sorted, a_k, "right")
            - np.searchsorted(b_sorted, a_k, "left")
        ).sum()
    )
    if join_rows + a_k.shape[0] > cap_rows:
        return None
    reps, ii = _expand_join(b_sorted, a_k)
    jj = order[ii]
    out_i = np.concatenate([a_i, a_i[reps]])
    out_j = np.concatenate([a_k, b_j[jj]])
    planes = [
        np.concatenate([a_v, np.minimum(a_v[reps], p[jj])]) for p in b_planes
    ]
    o2 = np.lexsort((out_j, out_i))
    out_i, out_j = out_i[o2], out_j[o2]
    first = np.ones(out_i.shape[0], bool)
    first[1:] = (out_i[1:] != out_i[:-1]) | (out_j[1:] != out_j[:-1])
    st = np.nonzero(first)[0]
    return (
        out_i[first], out_j[first],
        *[np.maximum.reduceat(p[o2], st) for p in planes],
    )


def tjoin_spmm(
    k1: np.ndarray, pe: np.ndarray, w: np.ndarray,
    cl_k1: np.ndarray, cl_k2: np.ndarray,
    c_d: np.ndarray, c_p: np.ndarray, cap_rows: int,
) -> Optional[Tuple[np.ndarray, ...]]:
    """The T-index join (flat.py ``_tindex_join``) as the host SpMM
    instance: A = userset entries (row-key k1, group-key pe, until w),
    B = the membership closure by target, planes = (definite, possible)
    untils.  Byte-for-byte the output of fold.py ``t_join_core`` — the
    bespoke kernel stays as the ``EngineConfig.spmm=False`` parity
    oracle (tests/test_spmm.py asserts equality on fuzzed worlds)."""
    return masked_semiring_spmm(
        k1, pe, w, cl_k2, cl_k1, (c_d, c_p), cap_rows
    )


# ---------------------------------------------------------------------------
# on-device set algebra (fixed shapes; the ⊕ short-circuit as bitmaps)
# ---------------------------------------------------------------------------


def _bm_mark(bm, ids, valid):
    """Set ``ids``' bits (ids sorted-unique among ``valid`` — distinct
    (word, bit) pairs, so the scatter-add is an exact OR)."""
    import jax.numpy as jnp

    word = jnp.where(valid, ids >> 5, 0)
    bit = jnp.where(
        valid,
        jnp.uint32(1) << (ids & 31).astype(jnp.uint32),
        jnp.uint32(0),
    )
    return bm.at[word].add(bit)


def _bm_unseen(bm, ids, valid):
    """``valid`` entries whose bit is still clear."""
    import jax.numpy as jnp

    word = jnp.where(valid, ids >> 5, 0)
    got = (bm[word] >> (jnp.where(valid, ids, 0) & 31).astype(jnp.uint32)) & 1
    return valid & (got == 0)


def _fresh(pool, valid, bm):
    """Sorted-unique not-yet-seen subset of ``pool`` (marked into
    ``bm``): returns (sorted pool, fresh mask, bm').  The device twin of
    spmv._Seen.fresh — dead lanes ride as the sort-last sentinel."""
    import jax.numpy as jnp

    x = jnp.sort(jnp.where(valid, pool, _SENT))
    ok = x != _SENT
    uniq = ok & jnp.concatenate(
        [jnp.ones((1,), bool), x[1:] != x[:-1]]
    )
    fresh = _bm_unseen(bm, x, uniq)
    return x, fresh, _bm_mark(bm, x, fresh)


def _compact(vals, mask, cap):
    """Masked entries packed order-stable into a fixed [cap] buffer
    (-1 fill): returns (buffer, count, overflowed)."""
    import jax.numpy as jnp

    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    cnt = jnp.sum(mask.astype(jnp.int32))
    out = jnp.full(cap, -1, jnp.int32).at[
        jnp.where(mask & (pos < cap), pos, cap)
    ].set(jnp.where(mask, vals, 0), mode="drop")
    return out, cnt, cnt > cap


def _append(buf, n, vals, mask, cap):
    """Masked entries appended at offset ``n`` of a fixed [cap] buffer:
    returns (buffer, n', overflowed)."""
    import jax.numpy as jnp

    pos = n + jnp.cumsum(mask.astype(jnp.int32)) - 1
    cnt = jnp.sum(mask.astype(jnp.int32))
    buf = buf.at[jnp.where(mask & (pos < cap), pos, cap)].set(
        jnp.where(mask, vals, 0), mode="drop"
    )
    return buf, jnp.minimum(n + cnt, cap), n + cnt > cap


# ---------------------------------------------------------------------------
# the fused K-hop programs (per-FlatMeta, cached on the engine)
# ---------------------------------------------------------------------------


class SpmmKernels:
    """The fused K-hop lookup programs of one FlatMeta geometry: the
    spmv probe/emission bodies composed under ``lax.while_loop``, all
    shapes static — one compiled executable per (meta, direction,
    snapshot table shapes), pinned the way engine/latency.py pins its
    small-batch tiers.  ``traces`` counts trace entries per direction
    (the no-retrace assertion reads it)."""

    def __init__(self, meta, config) -> None:
        import jax

        self.meta = meta
        self.F = _ceil_pow2(int(config.spmm_frontier), 256)
        self.E = _ceil_pow2(int(config.spmm_emit), 1024)
        self.C = int(config.spmm_candidates)
        self.K = int(config.spmm_rounds)
        self.traces = {"res": 0, "subj": 0}
        self._kern = None  # bound lazily (FrontierKernels of the meta)
        self._res_fn = None
        self._subj_fn = None
        self._cost_reg: set = set()
        self._jit = jax.jit

    def bind(self, kern) -> None:
        """Attach the meta's FrontierKernels (the raw probe/emit bodies
        the fused programs are composed from) and build the jits."""
        if self._kern is not None:
            return
        self._kern = kern
        self._res_fn = self._jit(self._build_resources())
        if self.meta.has_fw:
            self._subj_fn = self._jit(self._build_subjects())

    # -- reverse reachability: LookupResources ---------------------------
    def _build_resources(self):
        import jax.numpy as jnp
        from jax import lax

        kern = self._kern
        meta = self.meta
        N, S1 = meta.N, meta.S1
        logN = N.bit_length() - 1
        F, E, C, K = self.F, self.E, self.C, self.K
        # reverse arrows are fan-in ~1 per frontier node (a folder has
        # one parent), so the arrow emit runs at a fraction of the
        # userset emit — the emit lanes are the program's dominant
        # per-round cost and overflow just falls back to the looped path
        Ea = max(E // 4, 512)
        WK = (N * S1 + 31) // 32
        WN = (N + 31) // 32
        runs_rv = kern.raw_runs["rv"]
        emit_rv = kern.raw_emits["rv"]
        runs_ra = kern.raw_runs["ra"]
        emit_ra = kern.raw_emits["ra"]

        def fn(rv_off, rv_off_a, rvx, ra_off, ra_off_a, rax,
               nt_d, k2p1_d, chain_ok_d, child_ok_d, perm_tab_d,
               seed_keys, seed_nodes, rtid, now):
            self.traces["res"] += 1  # trace-time only: the pin witness
            n_types = child_ok_d.shape[0] - 1
            n_k1 = k2p1_d.shape[0]

            def rowt(nodes, valid):
                t = jnp.where(
                    valid, nt_d[jnp.where(valid, nodes, 0)], jnp.int32(-1)
                )
                return jnp.where(t < 0, n_types, t), t

            bm_k = _bm_mark(
                jnp.zeros(WK, jnp.uint32), seed_keys, seed_keys >= 0
            )
            bm_n = _bm_mark(
                jnp.zeros(WN, jnp.uint32), seed_nodes, seed_nodes >= 0
            )
            kf0 = jnp.full(F, -1, jnp.int32).at[: _SEED_KEYS].set(seed_keys)
            nf0 = jnp.full(F, -1, jnp.int32)

            def cond(c):
                kf, nf, _bk, _bn, _cd, _nc, ovf, r = c
                return (
                    (jnp.any(kf >= 0) | jnp.any(nf >= 0))
                    & ~ovf & (r < K)
                )

            def body(c):
                kf, nf, bm_k, bm_n, cand, ncand, ovf, r = c
                # one masked SpMV over the reverse userset view: which
                # (slot, resource) rows grant the frontier keys
                lo, ln = runs_rv(rv_off, rv_off_a, rvx, kf)
                rows, live = emit_rv(rvx, lo, ln, jnp.int32(0), now, E)
                ovf |= jnp.sum(ln) > E
                k1 = jnp.where(live, rows[:, 1], 0)
                res = k1 & jnp.int32(N - 1)
                slotd = k1 >> logN
                nk = k2p1_d[jnp.clip(slotd, 0, n_k1 - 1)].astype(jnp.int32)
                row_res, _t = rowt(res, live)
                chain = live & (nk > 0) & chain_ok_d[row_res, nk]
                ckeys = jnp.where(chain, res * jnp.int32(S1) + nk, -1)
                # one masked SpMV over the reverse arrows: parents of
                # the node frontier
                lo2, ln2 = runs_ra(ra_off, ra_off_a, rax, nf)
                rows2, live2 = emit_ra(rax, lo2, ln2, jnp.int32(0), now, Ea)
                ovf |= jnp.sum(ln2) > Ea
                par = jnp.where(live2, rows2[:, 1] & jnp.int32(N - 1), -1)
                # fresh nodes (⊕ short-circuit): candidates, arrow
                # children, permission-chain sources
                pool_n = jnp.concatenate(
                    [jnp.where(live, res, -1), par]
                )
                xn, freshn, bm_n = _fresh(pool_n, pool_n >= 0, bm_n)
                rown, tn = rowt(xn, freshn)
                cand, ncand, o1 = _append(
                    cand, ncand, xn, freshn & (tn == rtid), C
                )
                nf2, _cn, o2 = _compact(xn, freshn & child_ok_d[rown], F)
                pk = xn[:, None] * jnp.int32(S1) + perm_tab_d[rown]
                pkeys = jnp.where(
                    freshn[:, None] & (perm_tab_d[rown] > 0), pk, -1
                ).ravel()
                pool_k = jnp.concatenate([ckeys, pkeys])
                xk, freshk, bm_k = _fresh(pool_k, pool_k >= 0, bm_k)
                kf2, _ck, o3 = _compact(xk, freshk, F)
                return (
                    kf2, nf2, bm_k, bm_n, cand, ncand,
                    ovf | o1 | o2 | o3, r + 1,
                )

            kf, nf, bm_k, bm_n, cand, ncand, ovf, rounds = lax.while_loop(
                cond, body,
                (kf0, nf0, bm_k, bm_n, jnp.zeros(C, jnp.int32),
                 jnp.int32(0), jnp.bool_(False), jnp.int32(0)),
            )
            converged = ~(jnp.any(kf >= 0) | jnp.any(nf >= 0))
            return cand, ncand, ovf | ~converged, rounds

        return fn

    # -- forward reachability: LookupSubjects ----------------------------
    def _build_subjects(self):
        import jax.numpy as jnp
        from jax import lax

        kern = self._kern
        meta = self.meta
        N, S1 = meta.N, meta.S1
        F, E, C, K = self.F, self.E, self.C, self.K
        WN = (N + 31) // 32
        runs_fw = kern.raw_runs["fw"]
        emit_fw = kern.raw_emits["fw"]
        runs_arg = kern.raw_runs["arg"]
        emit_arg = kern.raw_emits["arg"]
        arg_aligned = kern._arg_aligned

        def fn(fw_off, fw_off_a, fwx, arg_p, arx,
               nt_d, slot_e_d, e_k1d_d, slot_ts_d, ts_k1d_d,
               k2p1_raw_d, k1d_d, perm_raw_d,
               seed_nodes, stid, srel_slot, wc_node, now):
            self.traces["subj"] += 1  # trace-time only
            n_types = perm_raw_d.shape[0] - 1
            num_slots = k1d_d.shape[0]
            NSp = num_slots + 1
            ES = e_k1d_d.shape[0]
            TS = ts_k1d_d.shape[0]
            WP = (N * NSp + 31) // 32

            def rowt(nodes, valid):
                t = jnp.where(
                    valid, nt_d[jnp.where(valid, nodes, 0)], jnp.int32(-1)
                )
                return jnp.where(t < 0, n_types, t), t

            bm_n = _bm_mark(
                jnp.zeros(WN, jnp.uint32), seed_nodes, seed_nodes >= 0
            )
            nf0 = jnp.full(F, -1, jnp.int32).at[: _SEED_NODES].set(seed_nodes)
            pf0 = jnp.full(F, -1, jnp.int32)

            def cond(c):
                nf, pf = c[0], c[1]
                ovf, r = c[-2], c[-1]
                return (
                    (jnp.any(nf >= 0) | jnp.any(pf >= 0))
                    & ~ovf & (r < K)
                )

            def body(c):
                (nf, pf, bm_n, bm_p, bm_c, cand, ncand,
                 gsr, ngsr, wc, ovf, r) = c
                valid_n = nf >= 0
                rown, _tn = rowt(nf, valid_n)
                # forward arrow hop (the argx range view)
                children = jnp.full(E, -1, jnp.int32)
                if TS:
                    tok = valid_n[:, None] & slot_ts_d[rown]
                    akeys = jnp.where(
                        tok,
                        nf[:, None] + ts_k1d_d[None, :] * jnp.int32(N),
                        -1,
                    ).ravel()
                    if arg_aligned:
                        lo, ln = runs_arg(arg_p, akeys)
                    else:
                        lo, ln = runs_arg(*arg_p, akeys)
                    rowsa, livea = emit_arg(
                        arx, lo, ln, jnp.int32(0), now, E
                    )
                    ovf |= jnp.sum(ln) > E
                    children = jnp.where(livea, rowsa[:, 0], -1)
                # forward edge hop: node keys + rel-pair keys in ONE
                # masked SpMV over the fw view
                valid_p = pf >= 0
                g = jnp.where(valid_p, pf // NSp, 0)
                rr = jnp.where(valid_p, pf % NSp, 0)
                rowg, _tg = rowt(g, valid_p)
                is_perm = valid_p & perm_raw_d[
                    rowg, jnp.clip(rr, 0, num_slots - 1)
                ] & (rr < num_slots)
                kd = k1d_d[jnp.clip(rr, 0, num_slots - 1)].astype(jnp.int32)
                relm = valid_p & ~is_perm & (kd >= 0) & (rr < num_slots)
                fkeys2 = jnp.where(relm, kd * jnp.int32(N) + g, -1)
                if ES:
                    eok = valid_n[:, None] & slot_e_d[rown]
                    fkeys1 = jnp.where(
                        eok,
                        nf[:, None] + e_k1d_d[None, :] * jnp.int32(N),
                        -1,
                    ).ravel()
                    fkeys = jnp.concatenate([fkeys1, fkeys2])
                else:
                    fkeys = fkeys2
                lo2, ln2 = runs_fw(fw_off, fw_off_a, fwx, fkeys)
                rowsf, livef = emit_fw(fwx, lo2, ln2, jnp.int32(0), now, E)
                ovf |= jnp.sum(ln2) > E
                k2v = jnp.where(livef, rowsf[:, 1], 0)
                direct = livef & (k2v % jnp.int32(S1) == 0)
                dn = k2v // jnp.int32(S1)
                wc = wc | jnp.any(direct & (dn == wc_node) & (wc_node >= 0))
                # direct subjects: candidates (deduped on-device)
                rowd, td = rowt(dn, direct)
                cpool = jnp.where(
                    direct & (td == stid) & (srel_slot < 0), dn, -1
                )
                xc, freshc, bm_c = _fresh(cpool, cpool >= 0, bm_c)
                cand, ncand, o1 = _append(cand, ncand, xc, freshc, C)
                # userset subjects: raw (group, relation) pairs
                um = livef & ~direct
                r2 = k2p1_raw_d[
                    jnp.where(um, k2v % jnp.int32(S1), 0)
                ].astype(jnp.int32)
                pairc = jnp.where(
                    um & (r2 >= 0),
                    (k2v // jnp.int32(S1)) * jnp.int32(NSp) + r2,
                    -1,
                )
                xp, freshp, bm_p = _fresh(pairc, pairc >= 0, bm_p)
                pf2, _cp, o2 = _compact(xp, freshp, F)
                srm = freshp & (srel_slot >= 0) & (
                    xp % jnp.int32(NSp) == srel_slot
                )
                gsr, ngsr, o3 = _append(
                    gsr, ngsr, xp // jnp.int32(NSp), srm, C
                )
                # next node frontier: arrow children + permission-pair
                # sources (holders of g#p ⊆ expansion of g)
                pool_n = jnp.concatenate(
                    [children, jnp.where(is_perm, g, -1)]
                )
                xn, freshn, bm_n = _fresh(pool_n, pool_n >= 0, bm_n)
                nf2, _cn, o4 = _compact(xn, freshn, F)
                return (
                    nf2, pf2, bm_n, bm_p, bm_c, cand, ncand, gsr, ngsr,
                    wc, ovf | o1 | o2 | o3 | o4, r + 1,
                )

            (nf, pf, _bn, _bp, _bc, cand, ncand, gsr, ngsr, wc, ovf,
             rounds) = lax.while_loop(
                cond, body,
                (
                    nf0, pf0, bm_n,
                    jnp.zeros(WP, jnp.uint32),
                    jnp.zeros(WN, jnp.uint32),
                    jnp.zeros(C, jnp.int32), jnp.int32(0),
                    jnp.zeros(C, jnp.int32), jnp.int32(0),
                    jnp.bool_(False), jnp.bool_(False), jnp.int32(0),
                ),
            )
            converged = ~(jnp.any(nf >= 0) | jnp.any(pf >= 0))
            return cand, ncand, gsr, ngsr, wc, ovf | ~converged, rounds

        return fn


def spmm_kernels_for(engine, meta) -> SpmmKernels:
    """Engine-level cache of the fused programs, keyed by meta — the
    same (snapshot, meta, tier) pin discipline engine/latency.py uses
    for CheckMany: geometry-identical snapshots share executables."""
    cache = engine.__dict__.setdefault("_spmm_kernels", {})
    k = cache.get(meta)
    if k is None:
        k = SpmmKernels(meta, engine.config)
        while len(cache) >= 8:
            cache.pop(next(iter(cache)))
        cache[meta] = k
    return k


# ---------------------------------------------------------------------------
# per-snapshot fused lookup server
# ---------------------------------------------------------------------------


def fused_ok(engine, st) -> bool:
    """Whether the fused K-hop path may serve this FrontierState.
    Sharded snapshots keep the owner-routed looped hops; key/pair
    domains must fit int32 (the on-device bitmap codes)."""
    cfg = engine.config
    if not getattr(cfg, "spmm", False):
        return False
    meta = st.meta
    if meta.sharded:
        return False
    num_slots = max(st.snap.num_slots, 1)
    if st.N * st.S1 >= 1 << 31 or st.N * (num_slots + 1) >= 1 << 31:
        return False
    return True


class FusedLookup:
    """One snapshot's fused-lookup server: the device constant tables
    (type map, pruning masks, permission chains) plus the dispatch
    wrappers.  Built by spmv.FrontierState when ``fused_ok``; answers
    are complete candidate sets from ONE dispatch, or ``None`` on
    overflow (the caller falls back to the looped path)."""

    def __init__(self, engine, st) -> None:
        import jax.numpy as jnp

        self.st = st
        self.kern = spmm_kernels_for(engine, st.meta)
        self.kern.bind(st.kern)
        N, S1 = st.N, st.S1
        snap = st.snap
        nt = np.full(N, -1, np.int32)
        nt[: snap.node_type.shape[0]] = snap.node_type.astype(np.int32)
        self.nt_d = jnp.asarray(nt)
        self.k2p1_d = jnp.asarray(st.k2p1_of_k1d.astype(np.int32))
        self.chain_ok_d = jnp.asarray(st.chain_ok)
        self.child_ok_d = jnp.asarray(st.child_ok)
        n_types = st.child_ok.shape[0] - 1
        # permission-userset chains only when the compiled schema has
        # any (the host gate: FrontierState.perm_chains)
        chains = st.perm_k2p1_of_tid if st.perm_chains else {}
        pmax = max([v.shape[0] for v in chains.values()] or [1])
        ptab = np.zeros((n_types + 1, pmax), np.int32)
        for t, k2p1 in chains.items():
            ptab[t, : k2p1.shape[0]] = k2p1.astype(np.int32)
        self.perm_tab_d = jnp.asarray(ptab)
        self._subj_ready = st.meta.has_fw and self.kern._subj_fn is not None
        if self._subj_ready:
            num_slots = max(snap.num_slots, 1)
            e_slot_raw = np.asarray(
                [s for s in st.meta.e_slots if st.k1d[s] >= 0], np.int64
            )
            ts_raw = np.asarray(
                [s for s in st.ts_slots if st.k1d[s] >= 0], np.int64
            )
            self.slot_e_d = jnp.asarray(
                st.slot_of_type[:, e_slot_raw]
                if e_slot_raw.size
                else np.zeros((n_types + 1, 0), bool)
            )
            self.e_k1d_d = jnp.asarray(
                st.k1d[e_slot_raw].astype(np.int32)
                if e_slot_raw.size else np.zeros(0, np.int32)
            )
            self.slot_ts_d = jnp.asarray(
                st.slot_of_type[:, ts_raw]
                if ts_raw.size
                else np.zeros((n_types + 1, 0), bool)
            )
            self.ts_k1d_d = jnp.asarray(
                st.k1d[ts_raw].astype(np.int32)
                if ts_raw.size else np.zeros(0, np.int32)
            )
            k2p1_raw = np.full(S1 + 1, -1, np.int32)
            for raw, d in enumerate(st.k2d):
                if d >= 0:
                    k2p1_raw[d + 1] = raw
            self.k2p1_raw_d = jnp.asarray(k2p1_raw)
            # pad the raw-slot→dense-k1 map to exactly num_slots so the
            # device pair encoding (g·(num_slots+1)+r) matches the host's
            k1p = np.full(num_slots, -1, np.int32)
            m = min(num_slots, st.k1d.shape[0])
            k1p[:m] = st.k1d[:m]
            self.k1d_d = jnp.asarray(k1p)
            self.perm_raw_d = jnp.asarray(
                np.vstack(
                    [st.perm_raw_table,
                     np.zeros((1, st.perm_raw_table.shape[1]), bool)]
                )
            )
        _ensure_report_section()

    # -- dispatch plumbing ----------------------------------------------
    def _dispatch(self, direction: str, fn, args):
        """One fused launch: the program's outputs on the host, its
        round counter taken off (counter ``spmm.rounds``, the root
        span's ``rounds``)."""
        import jax

        # a fused launch IS a lookup dispatch: both sites fire, so
        # chaos/retry coverage armed on either exercises this path
        faults.fire("lookup.dispatch")
        faults.fire("spmm.dispatch")
        _mt.inc("spmm.dispatches")
        _trace.count_if_active("dispatches")
        self._register_cost(direction, fn, args)
        sp = _trace.current()
        with _trace.stage("lookup.fused.enqueue", sp):
            out = fn(*args)
        with _trace.stage("lookup.fused.fetch", sp):
            *out, rounds = jax.device_get(out)
        _mt.inc("spmm.rounds", int(rounds))
        _trace.count_if_active("rounds", int(rounds))
        return out

    def _register_cost(self, direction: str, fn, args) -> None:
        # per-SpmmKernels (= per-meta) guard, same as the spmv hop path
        if direction in self.kern._cost_reg:
            return
        self.kern._cost_reg.add(direction)
        from ..utils import perf as _perf

        kern = self.kern
        key = (
            f"fused-{direction};F={kern.F};E={kern.E};K={kern.K}"
            f";meta={hash(self.st.meta) & 0xFFFFFFFF:08x}"
        )
        _perf.register_cost_thunk(
            "spmm", key,
            lambda fn=fn, avals=_perf.avals_of(args): fn.lower(
                *avals
            ).compile(),
        )

    # -- LookupResources: the whole reverse fixpoint, one dispatch -------
    def resources(
        self, rtid: int, subj_node: int, srel_slot: int, wc_node: int,
        now_us: Optional[int],
    ) -> Optional[List[np.ndarray]]:
        import jax.numpy as jnp

        st = self.st
        N, S1 = st.N, st.S1
        sp = _trace.current()
        with _trace.stage("lookup.args", sp):
            seeds: List[int] = []
            if 0 <= subj_node < N:
                if srel_slot < 0:
                    seeds.append(subj_node * S1)
                elif st.k2d[srel_slot] >= 0:
                    seeds.append(subj_node * S1 + int(st.k2d[srel_slot]) + 1)
            if 0 <= wc_node < N:
                seeds.append(wc_node * S1)
            sk = np.full(_SEED_KEYS, -1, np.int32)
            uniq = sorted(set(seeds))[:_SEED_KEYS]
            sk[: len(uniq)] = uniq
            sn = np.full(_SEED_NODES, -1, np.int32)
            if 0 <= subj_node < N:
                sn[0] = subj_node
            args = (
                *st.rv_args, *st.ra_args,
                self.nt_d, self.k2p1_d, self.chain_ok_d, self.child_ok_d,
                self.perm_tab_d,
                jnp.asarray(sk), jnp.asarray(sn),
                jnp.int32(rtid), st._now(now_us),
            )
        cand, ncand, ovf = self._dispatch("res", self.kern._res_fn, args)
        if bool(ovf):
            return None
        with _trace.stage("lookup.expand", sp):
            blocks: List[np.ndarray] = []
            nt_shape = st.snap.node_type.shape[0]
            if 0 <= subj_node < nt_shape and (
                int(st.snap.node_type[subj_node]) == rtid
            ):
                blocks.append(np.asarray([subj_node], np.int64))
            arr = np.asarray(cand[: int(ncand)], np.int64)
            if arr.size:
                blocks.append(arr)
        return blocks

    # -- LookupSubjects: the whole forward fixpoint, one dispatch --------
    def subjects(
        self, res_node: int, stid: int, srel_slot: int, wc_node: int,
        now_us: Optional[int],
    ) -> Optional[List[np.ndarray]]:
        if not self._subj_ready:
            return None
        import jax.numpy as jnp

        st = self.st
        N = st.N
        sp = _trace.current()
        with _trace.stage("lookup.args", sp):
            sn = np.full(_SEED_NODES, -1, np.int32)
            if 0 <= res_node < N:
                sn[0] = res_node
            arg_p = tuple(st.arg_args) if st.arg_aligned else st.arg_args
            args = (
                *st.fw_args, arg_p, st.arx,
                self.nt_d, self.slot_e_d, self.e_k1d_d,
                self.slot_ts_d, self.ts_k1d_d,
                self.k2p1_raw_d, self.k1d_d, self.perm_raw_d,
                jnp.asarray(sn),
                jnp.int32(stid), jnp.int32(srel_slot),
                jnp.int32(wc_node), st._now(now_us),
            )
        cand, ncand, gsr, ngsr, wc, ovf = self._dispatch(
            "subj", self.kern._subj_fn, args
        )
        if bool(ovf):
            return None
        # the program's candidates, then the trailing blocks mirroring the
        # walker/looped tail order
        with _trace.stage("lookup.expand", sp):
            blocks: List[np.ndarray] = []
            emitted: set = set()
            arr = np.asarray(cand[: int(ncand)], np.int64)
            if arr.size:
                blocks.append(arr)
                emitted.update(int(x) for x in arr)
            nt = st.snap.node_type
            if srel_slot >= 0 and int(ngsr):
                gs = np.unique(np.asarray(gsr[: int(ngsr)], np.int64))
                gs = gs[(gs >= 0) & (gs < nt.shape[0])]
                gs = gs[nt[gs] == stid]
                gs = np.asarray(
                    [g for g in gs if int(g) not in emitted], np.int64
                )
                if gs.size:
                    blocks.append(gs)
                    emitted.update(int(x) for x in gs)
            if (
                0 <= res_node < nt.shape[0]
                and int(nt[res_node]) == stid
                and res_node not in emitted
            ):
                blocks.append(np.asarray([res_node], np.int64))
                emitted.add(res_node)
            if bool(wc) and srel_slot < 0:
                subs = st.all_subjects()
                subs = subs[(subs >= 0) & (subs < nt.shape[0])]
                subs = subs[nt[subs] == stid]
                subs = np.asarray(
                    [s for s in subs if int(s) not in emitted], np.int64
                )
                if subs.size:
                    blocks.append(subs)
        return blocks


def fused_for(engine, st) -> Optional[FusedLookup]:
    """The FrontierState's fused server, or None when ineligible —
    the single construction gate spmv.py calls."""
    if not fused_ok(engine, st):
        return None
    return FusedLookup(engine, st)


# ---------------------------------------------------------------------------
# /perf visibility
# ---------------------------------------------------------------------------

_SECTION = [False]


def _ensure_report_section() -> None:
    """Ride the /perf payload (utils/perf.py report sections) with the
    fused core's serving counters — dispatches vs fallbacks is the
    fused-coverage ratio the roofline columns contextualize."""
    if _SECTION[0]:
        return
    _SECTION[0] = True
    from ..utils import perf as _perf

    def stats():
        return {
            "dispatches": _mt.counter("spmm.dispatches"),
            "fallbacks": _mt.counter("spmm.fallbacks"),
            "lookup_dispatches_looped": _mt.counter("lookup.dispatches"),
        }

    _perf.register_report_section("spmm", stats)
