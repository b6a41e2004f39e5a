"""The shipped layout of the enumeration tables (engine/rev.py: ``rvx``,
``fwx``, ``rax``) and the guard that no lookup program moves a whole one.

The tables ship FLAT — one dimension of n·stride lanes, row r at lanes
[r·stride, r·stride + stride), stride = w int32 columns or the uint16
lanes of the packed spec — because the kernels of engine/spmv.py index
them flat, and on the TPU flattening a narrow ``[n, w]`` operand inside a
program is a physical copy of the whole table: once a round of the fused
fixpoint loop (engine/spmm.py), once a looped hop.  The CPU cannot see a
TPU relayout; it can see the operation that asks for one, so the guard
walks the programs' jaxprs.  The last test compiles the fused programs
for a described v5e at docs10m's table sizes and reads the compiler's
own answer (no chip runs; it is skipped where no TPU compiler is)."""

import re

import numpy as np
import pytest

import test_lookup as tl
from gochugaru_tpu.engine import packed as pk
from gochugaru_tpu.engine import spmm, spmv
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.flat import build_flat_arrays_sharded
from gochugaru_tpu.engine.hash import mix32
from gochugaru_tpu.engine.partition import ShardSlices, _hash_cols
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.engine.rev import (
    REV_TABLES, build_rev_full, build_rev_partitioned, rev_geom, row_lanes,
)
from gochugaru_tpu.schema import compile_schema, parse_schema
from gochugaru_tpu.store.interner import Interner
from gochugaru_tpu.store.snapshot import build_snapshot

NOW = tl.NOW
OFF_OF = {"rvx": "rv_off", "fwx": "fw_off", "rax": "ra_off"}

#: what must never take a whole table: each is (or on the TPU becomes) a
#: pass over every byte of its operand
MOVERS = ("reshape", "transpose", "copy", "convert_element_type")


@pytest.fixture(scope="module")
def snapshot():
    """An RBAC world whose smallest enumeration table (rax, 6,000 arrow
    rows) is several times wider than anything the lookup programs build
    for themselves at the default capacities, so operand size tells a
    table from a frontier."""
    rels, *_ = tl.rbac_world(seed=5, n_users=300, n_teams=30, n_orgs=12,
                             n_repos=6_000)
    cs = compile_schema(parse_schema(tl.RBAC))
    return cs, build_snapshot(1, cs, Interner(), rels, epoch_us=NOW)


@pytest.fixture(scope="module", params=[True, False], ids=["packed", "raw"])
def prepared(request, snapshot):
    cs, snap = snapshot
    engine = DeviceEngine(
        cs, EngineConfig.for_schema(cs, flat_packed=request.param)
    )
    dsnap = engine.prepare(snap)
    assert dsnap.flat_meta.has_rev and dsnap.flat_meta.has_fw
    return engine, dsnap, request.param


def _strides(meta):
    """Lanes a row, as the kernels take them (rev.row_lanes)."""
    kern_w = {
        "rvx": 2 + 2 * meta.e_hascav + meta.e_hasexp,
        "fwx": 2 + 2 * meta.e_hascav + meta.e_hasexp,
        "rax": 2 + 2 * meta.ar_hascav + meta.ar_hasexp,
    }
    specs = dict(meta.packed)
    return {
        k: (row_lanes(specs.get(k), kern_w[k]), kern_w[k], specs.get(k))
        for k in REV_TABLES
    }


def _check_layout(arrays, meta, M: int, n_rows: dict) -> None:
    """Every shard's block of every table: flat, whole rows, each
    bucket's run sorted by row identity and hashed to that bucket, the
    rows of all shards the snapshot's, the padding untouched."""
    for key, (stride, w, spec) in _strides(meta).items():
        tbl = np.asarray(arrays[key])
        off = np.asarray(arrays[OFF_OF[key]]).astype(np.int64)
        if (OFF_OF[key] + "_a") in arrays:  # anchor + residual offsets
            anchor = np.asarray(arrays[OFF_OF[key] + "_a"]).astype(np.int64)
            shift = dict(meta.packed_off)[OFF_OF[key]]
            off = anchor[np.arange(off.shape[0]) >> shift] + off
        assert tbl.ndim == 1, (key, tbl.shape)
        assert tbl.dtype == (np.int32 if spec is None else np.uint16), key
        assert tbl.shape[0] % (M * stride) == 0, (key, tbl.shape, stride)
        per = tbl.shape[0] // M
        bpd = off.shape[0] // M - 1
        total = 0
        for s in range(M):
            blk = tbl[s * per : (s + 1) * per].reshape(-1, stride)
            o = off[s * (bpd + 1) : (s + 1) * (bpd + 1)]
            n = int(o[-1])
            total += n
            assert o[0] == 0 and np.all(np.diff(o) >= 0) and n <= blk.shape[0]
            rows = blk[:n] if spec is None else pk.unpack_rows(blk[:n], spec)
            assert rows.shape == (n, w)
            if spec is None:
                assert np.all(blk[n:] == -1), f"{key}: padding written"
            # the key column decides the bucket: owner from the high bits
            h = mix32([rows[:, 0]], np) & np.uint32(bpd * M - 1)
            bucket = np.repeat(np.arange(bpd), np.diff(o))
            assert np.array_equal(h, s * bpd + bucket), f"{key}: bucket"
            # rows of one bucket sorted by full identity: a key's run is
            # contiguous, which is all the bisect asks
            order = np.lexsort(
                tuple(rows[:, j] for j in reversed(range(w))) + (bucket,)
            )
            assert np.array_equal(order, np.arange(n)), f"{key}: not sorted"
        assert total == n_rows[key], (key, total, n_rows[key])


def _n_rows(snap) -> dict:
    return {
        "rvx": int(snap.e_rel.shape[0]),
        "fwx": int(snap.e_rel.shape[0]),
        "rax": int(snap.ar_child.shape[0]),
    }


# ---------------------------------------------------------------------------
# (b) the shipped arrays have the layout the kernels state
# ---------------------------------------------------------------------------


def test_prepared_tables_ship_flat(prepared, snapshot):
    _engine, dsnap, packed = prepared
    meta = dsnap.flat_meta
    got = {k for k, _ in meta.packed} & set(REV_TABLES)
    assert got == (set(REV_TABLES) if packed else set()), got
    _check_layout(dsnap.arrays, meta, 1, _n_rows(snapshot[1]))


@pytest.mark.parametrize("partition_first", [True, False],
                         ids=["partition_first", "build_full"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "raw"])
@pytest.mark.parametrize("M", [2, 4])
def test_stacked_tables_ship_flat(snapshot, M, packed, partition_first):
    cs, snap = snapshot
    cfg = EngineConfig.for_schema(
        cs, flat_packed=packed, flat_partition_build=partition_first,
        flat_partition_chunk=1 << 12,
    )
    arrays, meta, _f, _c = build_flat_arrays_sharded(snap, cfg, M)
    assert meta.sharded and meta.has_rev and meta.has_fw
    _check_layout(arrays, meta, M, _n_rows(snap))


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "raw"])
@pytest.mark.parametrize("M", [2, 4])
def test_partition_first_is_bitwise_build_full_over_flat(snapshot, M, packed):
    cs, snap = snapshot
    built = [
        build_flat_arrays_sharded(
            snap,
            EngineConfig.for_schema(
                cs, flat_packed=packed, flat_partition_build=part,
                flat_partition_chunk=1 << 12,
            ),
            M,
        )
        for part in (True, False)
    ]
    (a, ma, *_), (b, mb, *_) = built
    assert ma == mb
    for key in REV_TABLES + tuple(OFF_OF.values()):
        assert a[key].dtype == b[key].dtype and a[key].shape == b[key].shape
        assert np.array_equal(a[key], b[key]), key


@pytest.mark.parametrize("w", [2, 3, 5])
@pytest.mark.parametrize("M", [1, 2, 4])
def test_build_rev_partitioned_equals_full_and_owned_blocks(M, w):
    """The two builders over random columns: the same flat array, and an
    owned subset is exactly its shards' contiguous R_pad·w lanes."""
    rng = np.random.default_rng(100 * M + w)
    n = 20_000
    cols = [rng.integers(0, 3_000, n).astype(np.int32)] + [
        rng.integers(0, 1 << 20, n).astype(np.int32) for _ in range(w - 1)
    ]
    h = _hash_cols([cols[0]])
    geom = rev_geom(h, M)
    off_f, tbl_f = build_rev_full(h, cols, geom, w)
    off_p, tbl_p = build_rev_partitioned(
        h, lambda rows: [c[rows] for c in cols], geom, w
    )
    assert tbl_f.ndim == 1 and tbl_f.shape == (M * geom.R_pad * w,)
    assert np.array_equal(off_f, off_p) and np.array_equal(tbl_f, tbl_p)
    rows = tbl_f.reshape(M * geom.R_pad, w)
    live = rows[rows[:, 0] >= 0]
    assert live.shape[0] == n
    want = np.stack(cols, axis=1)
    assert np.array_equal(
        live[np.lexsort(live.T[::-1])], want[np.lexsort(want.T[::-1])]
    )
    owned = tuple(range(M))[::2]
    off_o, tbl_o = build_rev_partitioned(
        h, lambda rows: [c[rows] for c in cols], geom, w, owned=owned
    )
    assert isinstance(tbl_o, ShardSlices) and tbl_o.shape == tbl_f.shape
    per = geom.R_pad * w
    assert tbl_o.per == per and sorted(tbl_o.blocks) == list(owned)
    for s in owned:
        assert np.array_equal(tbl_o.blocks[s], tbl_f[s * per : (s + 1) * per])
        assert np.array_equal(
            off_o.blocks[s], off_f[s * off_o.per : (s + 1) * off_o.per]
        )


# ---------------------------------------------------------------------------
# (a) no program reshapes, transposes, copies or converts a whole table
# ---------------------------------------------------------------------------


def _walk(jaxpr):
    """Every equation, into the bodies of while / cond / pjit / shard_map
    / scan / custom calls alike: any parameter that holds a jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _movers(closed, limit: int):
    """(primitive, operand shape) of every MOVERS equation with an
    operand of ``limit`` elements or more."""
    bad = []
    for eqn in _walk(closed.jaxpr):
        if eqn.primitive.name not in MOVERS:
            continue
        for v in eqn.invars:
            shape = getattr(v.aval, "shape", ())
            if int(np.prod(shape, dtype=np.int64)) >= limit:
                bad.append((eqn.primitive.name, tuple(shape)))
    return bad


def _limit(arrays) -> int:
    return min(int(np.prod(arrays[k].shape)) for k in REV_TABLES)


@pytest.fixture(scope="module")
def fused_calls(prepared):
    """direction -> (jitted program, its arguments) of one fused
    LookupResources and one fused LookupSubjects dispatch, recorded at
    the one place both leave from."""
    engine, dsnap, _packed = prepared
    calls = {}
    orig = spmm.FusedLookup._dispatch

    def record(self, direction, fn, args):
        calls.setdefault(direction, (fn, args))
        return orig(self, direction, fn, args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spmm.FusedLookup, "_dispatch", record)
        st = spmv.state_for(engine, dsnap)
        list(st.resource_candidates(
            dsnap.snapshot.interner.type_lookup("repo"), 3, -1, -1, NOW
        ))
        list(st.subject_candidates(
            5, dsnap.snapshot.interner.type_lookup("user"), -1, -1, NOW
        ))
    assert set(calls) == {"res", "subj"}, set(calls)
    return calls


def test_walker_sees_a_reshape_inside_a_loop():
    """The guard's own guard: the parent's sin, in miniature, is caught
    inside a while body inside a jit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def prog(tbl, idx):
        def body(c):
            i, acc = c
            return i + 1, acc + tbl.reshape(-1)[idx * 2]

        return lax.while_loop(lambda c: c[0] < 3, body, (0, jnp.zeros(4, jnp.int32)))

    closed = jax.make_jaxpr(prog)(
        jnp.zeros((4096, 2), jnp.int32), jnp.arange(4, dtype=jnp.int32)
    )
    assert ("reshape", (4096, 2)) in _movers(closed, 8192)
    assert not _movers(closed, 8193)


@pytest.mark.parametrize("direction", ["res", "subj"])
def test_fused_programs_move_no_table(prepared, fused_calls, direction):
    import jax

    _engine, dsnap, _packed = prepared
    fn, args = fused_calls[direction]
    closed = jax.make_jaxpr(fn)(*args)
    names = {e.primitive.name for e in _walk(closed.jaxpr)}
    assert "while" in names and "gather" in names  # it did walk the loop
    assert _movers(closed, _limit(dsnap.arrays)) == []


@pytest.mark.parametrize("kind", ["rv", "ra", "fw", "arg"])
def test_hop_programs_move_no_table(prepared, kind):
    import jax

    engine, dsnap, _packed = prepared
    st = spmv.state_for(engine, dsnap)
    hop = st.kern._hops_fused.get(kind)
    if hop is None:
        assert kind == "arg" and st.arg_aligned
        pytest.skip("aligned argx: the arrow hop is a probe and an emit")
    args, emit_tbl = {
        "rv": (st.rv_args, st.rv_args[2]),
        "ra": (st.ra_args, st.ra_args[2]),
        "fw": (st.fw_args, st.fw_args[2]),
        "arg": (st.arg_args, st.arx),
    }[kind]
    keys = st.kern.pad_keys(np.arange(7, dtype=np.int32))
    closed = jax.make_jaxpr(hop)(*args, emit_tbl, keys, st._now(NOW))
    assert "gather" in {e.primitive.name for e in _walk(closed.jaxpr)}
    assert _movers(closed, _limit(dsnap.arrays)) == []


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "raw"])
def test_sharded_hop_programs_move_no_table(snapshot, packed):
    """The shard_mapped probe and emit of parallel/sharded.py: the same
    bodies over each shard's contiguous block."""
    import jax
    import jax.numpy as jnp

    from gochugaru_tpu.parallel import ShardedEngine, make_mesh

    cs, snap = snapshot
    M = 4
    # the sharded emit runs at the full lookup_chunk a shard (65,536 rows
    # by default, wider than this world's tables): keep the emitted
    # block under a shard's block so that size still tells them apart
    sh = ShardedEngine(
        cs, make_mesh(1, M),
        EngineConfig.for_schema(cs, flat_packed=packed, lookup_chunk=256),
    )
    ds = sh.prepare(snap)
    assert ds.flat_meta.sharded and ds.flat_meta.has_rev
    st = spmv.state_for(sh, ds)
    assert st._hops is not None and st._spmm is None
    limit = _limit(ds.arrays) // M  # a shard's block of the smallest one
    assert limit > 4 * 256 * 5
    for kind in ("rv", "ra", "fw"):
        off_key, tbl_key = st._hops._TABS[kind]
        off, tbl = ds.arrays[off_key], ds.arrays[tbl_key]
        runs, emit = st._hops._fn_pair(kind)
        keys = jnp.full(M * st.kern.F_min, -1, jnp.int32)
        cr = jax.make_jaxpr(runs)(off, st._hops._dummy, tbl, keys)
        lo, ln = runs(off, st._hops._dummy, tbl, keys)
        ce = jax.make_jaxpr(emit)(
            tbl, lo, ln, jnp.zeros(M, jnp.int32), jnp.asarray(st._now(NOW))
        )
        for closed in (cr, ce):
            assert "gather" in {e.primitive.name for e in _walk(closed.jaxpr)}
            assert _movers(closed, limit) == [], kind


# ---------------------------------------------------------------------------
# the compiler's own answer, for a described v5e at docs10m's sizes
# ---------------------------------------------------------------------------

DOCS10M_ROWS = {"rvx": 16_777_216, "fwx": 16_777_216, "rax": 2_097_152}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _whole_table_ops(hlo: str, tables: dict):
    """Synchronous HLO instructions (not gathers, not the compiler's own
    prefetch into faster memory) whose result or operand has a whole
    table's shape."""
    shapes = []
    for rows, stride in tables.values():
        shapes += [f"[{rows * stride}]", f"[{rows},{stride}]"]
    bad = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m and m.group(1) in ("copy", "reshape", "transpose", "convert",
                                "bitcast-convert") and any(
            s in line for s in shapes
        ):
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("direction", ["res", "subj"])
def test_v5e_compiles_fused_programs_without_a_table_copy(
    prepared, fused_calls, one_chip, direction
):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    _engine, dsnap, _packed = prepared
    fn, args = fused_calls[direction]
    strides = _strides(dsnap.flat_meta)
    tables = {k: (DOCS10M_ROWS[k], strides[k][0]) for k in REV_TABLES}
    big = {}
    for key in REV_TABLES:
        a = dsnap.arrays[key]
        big[id(a)] = (tables[key][0] * tables[key][1],)
        o = dsnap.arrays[OFF_OF[key]]
        big[id(o)] = (tables[key][0] + 1,)

    def described(a):
        if isinstance(a, tuple):
            return tuple(described(x) for x in a)
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(
            big.get(id(a), tuple(a.shape)), a.dtype, sharding=one_chip
        )

    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out, and the run silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = fn.lower(*[described(a) for a in args]).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    hlo = compiled.as_text()
    assert "while(" in hlo and "gather" in hlo
    assert _whole_table_ops(hlo, tables) == []
    # the parent's programs held an 8 GiB relayout of a 128 MiB table
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


#: the fold's key + until row tables at rbac10m_exp's row counts
EXP_ROWS = {"csr_gdp": (2_097_152, 3), "pfu_gku": (8_388_608, 2)}


def test_v5e_reads_an_expiring_folds_slices_a_row_gather_a_lane(
    one_chip, monkeypatch
):
    """The check program of a world whose fold slices expire, compiled
    for a described v5e with its row tables at rbac10m_exp's sizes: each
    lane of a slice is one row gather (``s32[B, 3]`` of ``csr_gdp``,
    ``s32[B, 2]`` of ``pfu_gku``), nothing reads a slice table as a flat
    column, and no copy, transpose or reshape takes a whole one."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    import test_expiry_engine as te
    from gochugaru_tpu import rel
    from gochugaru_tpu.utils.context import background

    w = te.build_world(6, NOW)
    c = te.load(w)
    rels = [rel.must_from_triple(f"repo:{r}", "read", f"user:{u}")
            for r, u in te.probes(w, 64, 6)]
    c.check(background(), te.CS, *rels)
    (dsnap,) = c._dsnap_cache.values()
    meta = dsnap.flat_meta
    assert meta.fold_until_rows and set(EXP_ROWS) <= set(dsnap.arrays)
    B = 4096
    queries, qctx = c._engine._lower_queries(
        dsnap.snapshot, (rels * (B // len(rels) + 1))[:B], dsnap.strings
    )
    # the program keys its read forms off the default backend at trace
    # time: trace the TPU ones
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = c._engine.flat_fn_and_args(
        dsnap, queries, qctx, jnp.int32(0), B, jit=False, bucket_min=B
    )
    big = {id(dsnap.arrays[k]): shape for k, shape in EXP_ROWS.items()}

    def described(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(
            big.get(id(a), tuple(a.shape)), a.dtype, sharding=one_chip
        )

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(fn).lower(
            *jax.tree_util.tree_map(described, args)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    hlo = compiled.as_text()

    def gathers(shape):
        return len(re.findall(rf"= s32\[{shape}\]\{{[^}}]*\}} gather\(", hlo))

    slices = 2 if meta.has_wc_closure else 1
    assert gathers(f"{B},3") == meta.pf_s_fan * slices
    assert gathers(f"{B},2") > 0 and gathers(f"{B},2") % meta.pf_u_fan == 0
    for rows, _w in EXP_ROWS.values():
        assert f"s32[{rows}]" not in hlo  # no flat view of a slice table
    assert _whole_table_ops(hlo, {k: s for k, s in EXP_ROWS.items()}) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
