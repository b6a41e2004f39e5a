"""Static device-program structure compiled from a schema.

``build_plan`` turns a CompiledSchema into the *static* structure the JAX
engine's codegen closes over: tupleset slot numbering, the relation slots
that need leaf tests, permission expressions lowered to nested tuples, a
global topological update order, and schema-derived iteration bounds.  None
of this touches tuple data — it is fixed at WriteSchema time, so the jitted
check function is traced once per (schema, config, shape-bucket).

``EngineConfig`` holds the static capacity caps (SURVEY.md §7 "hard parts":
hop caps must be provably sufficient for non-recursive schemas — the
``for_schema`` constructor derives them from the compiler's depth analysis;
recursive schemas fall back to configurable caps with overflow detection
and host-oracle fallback).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple

from ..schema.ast import (
    Arrow,
    Exclusion,
    Expr,
    Intersection,
    Nil,
    RelationRef,
    Union,
)
from ..schema.compiler import CompiledSchema, _expr_refs


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _auto(knob: Optional[bool]) -> bool:
    """A None = auto tri-state: on when the default backend is a TPU."""
    return _on_tpu() if knob is None else bool(knob)

# Expression IR: nested tuples, all leaves static ints.
#   ("ref", slot) ("arrow", ts_idx, right_slot) ("union", (c...))
#   ("inter", (c...)) ("excl", base, sub) ("nil",)
ExprIR = tuple


@dataclass(frozen=True)
class EngineConfig:
    """Static capacity caps for the device evaluator.  Every cap has an
    overflow flag on device; overflowing queries are re-checked on the host
    oracle, so caps trade device coverage for speed, never correctness."""

    closure_size: int = 256  # max usersets a subject transitively belongs to
    seed_cap: int = 64  # max direct group memberships gathered per subject
    prop_cap: int = 8  # max parents per userset per closure hop
    closure_hops: int = 8  # userset-nesting depth walked on device
    subgraph_nodes: int = 8  # max arrow-reachable nodes per resource
    arrow_fanout: int = 4  # max tuples walked per (node, tupleset relation)
    us_leaf_cap: int = 8  # max userset grants tested per (node, relation)
    eval_iters: int = 2  # fixpoint iterations over the rewrite system
    batch_bucket_min: int = 8  # pad batch/unique-subject counts to pow2 ≥ this
    # -- flat (hash-probe) engine caps (engine/flat.py) -----------------
    use_flat: bool = True  # single-chip checks use the flat kernel
    flat_recursion: int = 8  # inline budget per recursive (type, slot) pair
    flat_max_slots: int = 8  # max distinct permissions per flat dispatch
    closure_source_cap: int = 4096  # max flattened pairs per closure source
    #: max product of arrow-child dims per query in the unrolled lattice;
    #: beyond it an arrow probes child-existence only (possible → host)
    flat_max_width: int = 256
    #: materialize the userset-grant join index (engine/flat.py T-index):
    #: us-edges ⋈ closure, so a userset grant test is ONE hash probe
    flat_tindex: bool = True
    #: T-index size budget as a multiple of the userset row count;
    #: exceeding it disables the index (KU probe path still answers)
    flat_tindex_factor: int = 64
    #: block-slice table layout: bucket-ordered interleaved tables probed
    #: with ONE contiguous [cap, w] slice per query (engine/hash.py) — ~2
    #: gathers per probe site instead of 2 + cap·(1 + nkey) scattered ones.
    #: TPU gathers cost ~a row per cycle regardless of width, so this is
    #: the TPU-shaped layout; False falls back to scattered 1-D probes
    flat_blockslice: bool = True
    #: accumulated delta-level rows (adds + tombstones) beyond
    #: max(this, E/8) trigger compaction: the next prepare rebuilds the
    #: base instead of growing the overlay (engine/flat.py delta level)
    flat_delta_min_compact: int = 65_536
    #: host-side mirror of the same bound: overlay rows beyond
    #: max(this, E/8) make store/delta.py materialize the LSM chain into
    #: a fresh base instead of deferring the merge.  Lower keeps probe
    #: depth (and find_in_view cost) small at the price of more frequent
    #: O(E) merges; the background chain compactor (store/group.py)
    #: works against half this trip so the merge lands off the write
    #: path.  Tunable (tune/tuner.py) off chain-depth telemetry
    lsm_compact_min: int = 65_536
    #: prewarm the transposed lookup index in a background thread at full
    #: prepare time (worlds ≥ LOOKUP_PREWARM_MIN_EDGES edges): cold
    #: lookup_resources joins a mostly-finished build instead of paying
    #: the O(E log E) sort inside the first user-facing query.  Only
    #: engaged when the HOST walker would serve lookups — snapshots
    #: carrying the reverse-CSR index (flat_rev_index) answer on the
    #: device frontier path and never need the transposed host index
    lookup_prewarm: bool = True
    #: build the reverse-CSR lookup index alongside the forward tables
    #: (engine/rev.py: rvx/rax/fwx + offsets): LookupResources/
    #: LookupSubjects then run as device-resident masked frontier SpMV
    #: (engine/spmv.py) instead of the host walker.  Costs ~16-24 packed
    #: bytes/edge of extra residency; False falls back to the walker
    flat_rev_index: bool = True
    #: per-dispatch row budget of the frontier expansion kernel: each
    #: hop emits matches in chunks of this many rows (fixed shape — one
    #: compiled program regardless of fan-out)
    lookup_chunk: int = 65_536
    #: frontier-key padding floor (pow2 tiers above it): bounds expansion
    #: kernel retraces the way batch_bucket_min bounds check dispatches
    lookup_frontier_min: int = 1_024
    #: dl_* table shape floor: delta tables pre-size to this many rows so
    #: consecutive revisions keep ONE compiled kernel instead of
    #: retracing at every pow2 row-count boundary (a retrace costs ~1s —
    #: the dominant term of the Watch-reindex loop without the floor);
    #: beyond the floor, shapes double (log-many retraces per chain)
    flat_delta_floor: int = 16_384
    #: flatten self-recursive arrow hierarchies into precomputed ancestor
    #: closures (the resource-side Leopard index, engine/flat.py
    #: rc_candidates/_arrow_closure): a depth-D folder tree evaluates in
    #: ONE level instead of D unrolled recursion levels
    flat_rc_index: bool = True
    #: fold whole union/arrow-chain permission rewrites into root-level
    #: probe tables (engine/fold.py P-index): a 5-hop nested check
    #: becomes ~2 probes; ineligible shapes keep the walked path
    flat_fold: bool = True
    #: folded row budget as a multiple of (E + US) row counts; pairs
    #: beyond it stay on the walked path
    flat_fold_factor: int = 16
    #: max userset-group fan per folded (slot, resource) in the pf_u
    #: range table (engine/fold.py fold_userset_rows — the factored
    #: replacement for the round-5 dense fold T-join).  A resource whose
    #: folded group list exceeds this would blow the kernel's per-query
    #: slice width, so the fold declines and the walked path answers
    flat_fold_u_fan_cap: int = 64
    #: max closure rows per SOURCE in the fold's subject-side slice (the
    #: csr closure-by-source view): the kernel intersects the resource's
    #: pf_u group list with the subject's group closure as a pure
    #: [u_fan × s_fan] register compare — no per-group gathers — so this
    #: bounds that compare tile.  A world whose hottest subject belongs
    #: to more groups declines the fold (walked path answers)
    flat_fold_subj_fan_cap: int = 64
    #: per-array entry budget for the fold's DIRECT offset arrays
    #: (pfu_start: fold-slots·N entries; csr_start: N·S1 entries) —
    #: two element gathers replace a hash probe per range lookup.  Key
    #: spaces beyond it keep the hash group tables
    flat_pf_direct_max_entries: int = 1 << 25
    #: incremental fold maintenance (engine/fold.py fold_delta_update):
    #: max total dirty resources per delta chain.  Past it the chain
    #: DOWNGRADES folded pairs to their walked programs (sticky pf_off
    #: until compaction re-folds the base) — a delta touching a hot
    #: ancestor can dirty a whole subtree, and recomputing that each
    #: revision would cost more than walking
    flat_fold_delta_dirty_cap: int = 16_384
    #: advance the flattened membership closure in place on membership-
    #: subgraph deltas (store/closure.py advance_closure) instead of
    #: bailing to a full prepare — the O(Δ·depth) write path
    closure_delta: bool = True
    #: max affected closure sources per advance; a delta whose reverse
    #: reachability fans past this rebuilds instead (a hot group touched
    #: near the nesting root can implicate everything below it)
    closure_delta_affected_cap: int = 65_536
    #: max accumulated T-index-dirty resource keys per delta chain.
    #: Membership deltas stale the baked T rows of every resource whose
    #: userset group changed; past this bound the chain flips the
    #: T-index OFF (sticky, like pf_off) and the KU path — which probes
    #: the live closure directly — answers those slots until compaction
    flat_tindex_dirty_cap: int = 65_536
    #: bucket-ALIGNED probe tables (engine/hash.py build_aligned): each
    #: bucket is ONE table row fetched with a single row gather (rate on
    #: a TPU against the off+block layout: not measured).  None = auto
    #: (on when the default backend is tpu); tests force True to
    #: exercise the layout on CPU
    flat_aligned: Optional[bool] = None
    #: per-table byte budget for the aligned layout; tables whose aligned
    #: form exceeds it keep the off+interleave layout
    flat_aligned_max_bytes: int = 3 << 30
    #: width-stratification ladder for the aligned layout
    #: (engine/hash.py build_aligned ``cover``): level i's row width is
    #: the smallest cap covering this share of its entries, overflow
    #: cascades to the next (salted) level, and a fit-all level closes
    #: the ladder.  The 1-entry default is the classic primary+spill
    #: pair; (0.99, 0.999) buys a narrower primary row — most of the
    #: table's bytes — for one extra single-gather level
    flat_aligned_cover: Tuple[float, ...] = (0.999,)
    # -- HBM-lean packed tables (engine/packed.py) -----------------------
    #: bit-packed device tables: logical int32 columns share uint16
    #: lanes (keys at their radix widths, caveat/ctx ids at their count
    #: widths, range ends as delta-run lengths, until-values as small
    #: dictionaries), bucket offsets split into int32 anchors + uint16
    #: residuals, and point-table bucket growth is bounded by
    #: ``flat_packed_max_factor`` instead of chasing cap ≤ 4 through 8x
    #: offsets.  The kernel decodes with shift/mask ops fused into the
    #: existing block gathers — bitwise-identical query results, ~3-6x
    #: fewer resident table bytes (BENCHMARKS.md "HBM-lean tables").
    #: None = auto (on whenever the blockslice layout is); False is the
    #: parity oracle (the exact pre-packing layout)
    flat_packed: Optional[bool] = None
    #: bucket-count growth bound for the packed layout's hash builds
    #: (size ≤ this x pow2(2n)): a deeper probe cap costs a few fused
    #: compares; an 8x offsets array costs hundreds of MB of HBM
    flat_packed_max_factor: int = 2

    def packed_on(self) -> bool:
        """The resolved flat_packed flag (None = auto: packed whenever
        the blockslice layout is active — the scattered layout keeps
        full-width columns)."""
        if self.flat_packed is not None:
            return bool(self.flat_packed) and self.flat_blockslice
        return self.flat_blockslice
    #: partition-first stacked builds (engine/partition.py): hash keys to
    #: bucket shards FIRST, then build each model shard's slice of the
    #: stacked tables independently — bitwise-identical output with
    #: O(E/M) sort/hash/interleave scratch per shard instead of O(E)
    #: (ROADMAP "Host-sharded table build").  False keeps the reference
    #: build-full-then-stack path (the parity tests' oracle)
    flat_partition_build: bool = True
    #: row-chunk size of the partitioned build's primary-key hash pass:
    #: the dense (k1, k2) packs are computed per chunk, so no full-size
    #: O(E) packed key column is ever materialized (the bound
    #: tests/test_sharded_memory.py's allocation tracker asserts)
    flat_partition_chunk: int = 1 << 22
    #: bulk-check batches beyond this split into sub-dispatches queued
    #: back-to-back (jax async dispatch): device compute overlaps the
    #: next chunk's host lowering/transfer and per-sub-batch results
    #: land early (BASELINE config-4 tail, VERDICT r04 item 8).  None =
    #: auto: 32768 on TPU (queued dispatches genuinely overlap), off on
    #: CPU (one core executes chunks serially and the per-dispatch
    #: overhead costs ~40% throughput — measured, bench4).  0 disables
    flat_pipeline_batch: Optional[int] = None
    # -- latency-mode execution path (engine/latency.py) -----------------
    #: small-batch padding tiers: a latency-mode batch pads to the
    #: smallest tier ≥ B and runs a pinned AOT-compiled kernel for that
    #: tier — a handful of tiers bounds the pinned-executable count
    #: while keeping pad waste bounded; batches beyond the top tier use
    #: the throughput path.  Any sorted tuple of positive ints works —
    #: tiers need NOT be powers of two; the offline tuner
    #: (gochugaru_tpu/tune) emits workload-fit ladders like (192, 576,
    #: 4096) and the no-retrace contract holds because pins are keyed
    #: by the tier value itself, not its log2
    latency_tiers: Tuple[int, ...] = (256, 1024, 4096)
    # -- unified masked-SpMM sparse core (engine/spmm.py) ----------------
    #: serve multi-hop lookups through the fused K-hop SpMM program (the
    #: whole reverse/forward frontier fixpoint in ONE pinned dispatch,
    #: frontier carried on-device between hops) and route the fold
    #: T-join through the same semiring primitive.  False is the parity
    #: oracle: the per-hop looped spmv path and the bespoke t_join_core,
    #: byte-for-byte (the flat_packed=False-style lever)
    spmm: bool = True
    #: max fused hop rounds per dispatch; a frontier still live after
    #: this many rounds overflows to the looped path
    spmm_rounds: int = 10
    #: on-device frontier capacity per round (keys AND nodes, pow2);
    #: wider frontiers overflow to the looped path — bulk subjects with
    #: ~1M-candidate answers are the looped path's workload anyway
    spmm_frontier: int = 1_024
    #: per-round emission budget of each fused probe (pow2).  The emit
    #: lanes run at full static width every round, so this is the fused
    #: program's dominant cost — size for the common lookup, not the
    #: worst case: overflow falls back to the looped path correctly
    spmm_emit: int = 2_048
    #: candidate-buffer capacity of one fused dispatch; answers larger
    #: than this overflow to the looped (streaming) path
    spmm_candidates: int = 8_192

    # -- the backend-keyed choices, resolved in ONE place -----------------
    def aligned_on(self) -> bool:
        """Resolved flat_aligned (None = auto: on for TPU)."""
        return _auto(self.flat_aligned)

    def pipeline_batch(self) -> int:
        """Resolved flat_pipeline_batch (None = auto: 32768 on TPU, 0 —
        no sub-batch pipeline — elsewhere)."""
        if self.flat_pipeline_batch is not None:
            return int(self.flat_pipeline_batch)
        return 32_768 if _on_tpu() else 0

    def resolved(self) -> Dict[str, object]:
        """What every None = auto knob resolves to in this process —
        the record an entry script prints next to its results."""
        return {
            "flat_aligned": self.aligned_on(),
            "flat_packed": self.packed_on(),
            "flat_pipeline_batch": self.pipeline_batch(),
        }

    @staticmethod
    def for_schema(compiled: CompiledSchema, **overrides) -> "EngineConfig":
        cfg = EngineConfig()
        userset_depth = _userset_depth(compiled)
        arrow_depth = _arrow_depth(compiled)
        if userset_depth == 0:
            cfg = replace(cfg, closure_hops=0)
        elif userset_depth > 0:
            cfg = replace(cfg, closure_hops=min(userset_depth, cfg.closure_hops))
        # -1 (cyclic): keep the default cap.
        if arrow_depth == 0:
            cfg = replace(cfg, subgraph_nodes=1)
        elif arrow_depth > 0:
            # acyclic arrows: the subgraph is as deep as the longest
            # type-level arrow chain (fanout beyond the cap overflows to the
            # host).
            cfg = replace(cfg, subgraph_nodes=max(2, min(1 + 2 * arrow_depth, 32)))
        # else keep the default subgraph cap (recursive hierarchies).
        # Fixpoint iterations: one topo-ordered pass resolves any acyclic
        # rewrite system; cycles through *evaluation* dependencies (mutually
        # recursive permissions, recursive arrows) propagate one dependency
        # step per iteration, so the bound must cover the cycle length AND
        # the subgraph chain length.  Userset (group) recursion is the
        # closure phase's job and does not force iterations here.
        rec = _eval_recursion_bound(compiled)
        if rec == 0:
            cfg = replace(cfg, eval_iters=1)
        else:
            cfg = replace(
                cfg, eval_iters=min(32, max(cfg.subgraph_nodes, rec + 1))
            )
        return replace(cfg, **overrides)


def _longest_path(edges: Dict) -> Tuple[int, set]:
    """Longest path length over an adjacency dict {node: iterable(node)}.
    Returns (depth, cyclic_nodes): depth is -1 if cyclic; cyclic_nodes are
    the nodes observed on a cycle."""
    if not edges:
        return 0, set()
    memo: Dict = {}
    stack: List = []
    on_stack: set = set()
    cyclic_nodes: set = set()

    def depth(node) -> int:
        if node in memo:
            return memo[node]
        if node in on_stack:
            # every node from the first occurrence onward is on the cycle
            i = stack.index(node)
            cyclic_nodes.update(stack[i:])
            return 0
        stack.append(node)
        on_stack.add(node)
        d = 0
        for nxt in edges.get(node, ()):  # noqa: B905
            d = max(d, 1 + depth(nxt))
        stack.pop()
        on_stack.discard(node)
        memo[node] = d
        return d

    m = max(depth(n) for n in list(edges))
    return (-1 if cyclic_nodes else m), cyclic_nodes


def _userset_depth(compiled: CompiledSchema) -> int:
    """Nesting depth of the relation-userset graph: 0 = no relation admits
    userset subjects; -1 = cyclic (groups-in-groups); else the max depth."""
    edges: Dict[Tuple[str, str], List[Tuple[str, str]]] = {}
    for tname, d in compiled.schema.definitions.items():
        for rname, relation in d.relations.items():
            for a in relation.allowed:
                if a.relation:
                    edges.setdefault((tname, rname), []).append((a.type, a.relation))
    depth, _ = _longest_path(edges)
    return depth


def _arrow_depth(compiled: CompiledSchema) -> int:
    """Longest type-level chain of arrow (tupleset) traversals: 0 = no
    arrows, -1 = cyclic (recursive hierarchies), else the max chain length.
    This bounds the resource-subgraph BFS, which only walks arrow edges —
    far tighter than the full item-dependency depth."""
    edges: Dict[str, set] = {}
    for tname, d in compiled.schema.definitions.items():
        for perm in d.permissions.values():
            for ref in _expr_refs(perm.expr):
                if isinstance(ref, Arrow):
                    for a in d.relations[ref.left].allowed:
                        if not a.wildcard:
                            edges.setdefault(tname, set()).add(a.type)
    depth, _ = _longest_path(edges)
    return depth


def _eval_dep_graph(
    compiled: CompiledSchema,
) -> Dict[Tuple[str, str], List[Tuple[str, str]]]:
    """Evaluation-dependency graph over (type, item): permissions depend on
    same-type references and arrow targets; relations are leaves (their
    userset indirection is resolved by the closure phase)."""
    schema = compiled.schema
    edges: Dict[Tuple[str, str], List[Tuple[str, str]]] = {}
    for tname, d in schema.definitions.items():
        for pname, perm in d.permissions.items():
            deps: List[Tuple[str, str]] = []
            for ref in _expr_refs(perm.expr):
                if isinstance(ref, RelationRef):
                    deps.append((tname, ref.name))
                elif isinstance(ref, Arrow):
                    for a in d.relations[ref.left].allowed:
                        if not a.wildcard and schema.definitions[a.type].item(ref.right):
                            deps.append((a.type, ref.right))
            edges[(tname, pname)] = deps
    return edges


def _eval_recursion_bound(compiled: CompiledSchema) -> int:
    """Cycle bound for the fixpoint ITERATION (not the closure).  Returns 0
    if acyclic, else the number of nodes observed on cycles — an upper
    bound on the extra propagation steps a cycle needs."""
    depth, cyclic_nodes = _longest_path(_eval_dep_graph(compiled))
    if depth >= 0:
        return 0
    return max(1, len(cyclic_nodes))


def _eval_cyclic_pairs(compiled: CompiledSchema) -> frozenset:
    """(type_name, slot) pairs on an evaluation-dependency cycle — the
    pairs whose static unrolling needs a recursion budget (engine/flat.py);
    everything else terminates by schema acyclicity."""
    _, cyclic_nodes = _longest_path(_eval_dep_graph(compiled))
    return frozenset(
        (tname, compiled.slot_of_name[iname]) for tname, iname in cyclic_nodes
    )


@dataclass(frozen=True)
class TypeProgram:
    type_name: str
    schema_tid: int
    #: (perm_slot, expr_ir) pairs for this type
    perms: Tuple[Tuple[int, ExprIR], ...]


@dataclass(frozen=True)
class DevicePlan:
    """Everything static the device codegen needs."""

    ts_slots: Tuple[int, ...]  # tupleset slots; index = ts_idx in arrays
    rel_leaf_slots: Tuple[int, ...]  # relation slots needing leaf tests
    #: (type_name, schema_tid, perm_slot, expr_ir), globally topo-ordered by
    #: dependency depth so one fixpoint iteration resolves any acyclic chain
    topo_programs: Tuple[Tuple[str, int, int, ExprIR], ...]
    num_slots: int
    two_plane: bool  # caveats present → track (definite, possible) planes
    has_permission_usersets: bool
    num_schema_types: int


def _lower_expr(
    e: Expr, ts_index: Dict[int, int], slot_of: Dict[str, int]
) -> ExprIR:
    if isinstance(e, RelationRef):
        return ("ref", slot_of[e.name])
    if isinstance(e, Arrow):
        return ("arrow", ts_index[slot_of[e.left]], slot_of[e.right])
    if isinstance(e, Union):
        return ("union", tuple(_lower_expr(c, ts_index, slot_of) for c in e.children))
    if isinstance(e, Intersection):
        return ("inter", tuple(_lower_expr(c, ts_index, slot_of) for c in e.children))
    if isinstance(e, Exclusion):
        return (
            "excl",
            _lower_expr(e.base, ts_index, slot_of),
            _lower_expr(e.subtracted, ts_index, slot_of),
        )
    if isinstance(e, Nil):
        return ("nil",)
    raise TypeError(f"unknown expression node {e!r}")


def build_plan(compiled: CompiledSchema) -> DevicePlan:
    ts_slots = tuple(sorted(compiled.tupleset_slots))
    ts_index = {slot: i for i, slot in enumerate(ts_slots)}
    slot_of = compiled.slot_of_name

    rel_leaf = set()
    for d in compiled.schema.definitions.values():
        for rname in d.relations:
            rel_leaf.add(slot_of[rname])

    programs: List[Tuple[str, int, int, ExprIR]] = []
    for tname, d in compiled.schema.definitions.items():
        tid = compiled.type_ids[tname]
        for pname, perm in d.permissions.items():
            programs.append(
                (
                    tname,
                    tid,
                    slot_of[pname],
                    _lower_expr(perm.expr, ts_index, slot_of),
                )
            )
    # Global topological order by dependency depth: shallow first, so within
    # one iteration every acyclic dependency is already updated when read.
    programs.sort(key=lambda p: (compiled.item_depths.get((p[0], _name_of(compiled, p[2])), 0), p[0], p[2]))

    return DevicePlan(
        ts_slots=ts_slots,
        rel_leaf_slots=tuple(sorted(rel_leaf)),
        topo_programs=tuple(programs),
        num_slots=max(compiled.num_slots, 1),
        two_plane=bool(compiled.schema.caveats),
        has_permission_usersets=compiled.has_permission_usersets,
        num_schema_types=len(compiled.type_ids),
    )


def _name_of(compiled: CompiledSchema, slot: int) -> str:
    for name, s in compiled.slot_of_name.items():
        if s == slot:
            return name
    return ""
