"""Tests for the bucketed hash indexes (engine/hash.py): host/device hash
agreement, exact probes, range probes, duplicates, and empties."""

import numpy as np
import pytest

import jax.numpy as jnp

from gochugaru_tpu.engine.hash import (
    build_hash,
    build_range_hash,
    mix32,
    probe_range,
    probe_rows,
)


def test_mix32_host_device_agree():
    rng = np.random.default_rng(0)
    cols = [rng.integers(-(2**31), 2**31 - 1, 257).astype(np.int32) for _ in range(4)]
    hn = mix32(cols, np)
    hj = np.asarray(mix32([jnp.asarray(c) for c in cols], jnp))
    np.testing.assert_array_equal(hn, hj)


def _probe_host(idx, key_cols, q_cols):
    dev = [jnp.asarray(c) for c in key_cols]
    q = [jnp.asarray(c) for c in q_cols]
    return np.asarray(
        probe_rows(
            jnp.asarray(idx.off), jnp.asarray(idx.rows), dev, q, idx.cap, idx.n
        )
    )


def test_exact_probe_hits_and_misses():
    rng = np.random.default_rng(1)
    n = 5000
    k1 = rng.permutation(n).astype(np.int32)
    k2 = rng.integers(0, 50, n).astype(np.int32)
    k3 = rng.integers(-5, 5, n).astype(np.int32)
    idx = build_hash([k1, k2, k3])
    assert idx.cap <= 4 or idx.size >= 2 * n
    # every present key found at its own row
    got = _probe_host(idx, [k1, k2, k3], [k1, k2, k3])
    np.testing.assert_array_equal(got, np.arange(n))
    # absent keys miss
    qa = (k1 + np.int32(n)).astype(np.int32)  # k1 values all < n, so +n misses
    got = _probe_host(idx, [k1, k2, k3], [qa, k2, k3])
    assert (got == -1).all()


def test_duplicate_keys_probe_returns_a_matching_row():
    k1 = np.asarray([7, 7, 7, 3], np.int32)
    k2 = np.asarray([1, 1, 1, 2], np.int32)
    idx = build_hash([k1, k2])
    got = _probe_host(idx, [k1, k2], [np.asarray([7, 3], np.int32),
                                      np.asarray([1, 2], np.int32)])
    assert k1[got[0]] == 7 and k2[got[0]] == 1
    assert got[1] == 3


def test_empty_table_probes_miss():
    idx = build_hash([])
    got = _probe_host(
        idx,
        [np.zeros(1, np.int32)],
        [np.asarray([5, 0, -1], np.int32)],
    )
    assert (got == -1).all()


def test_probe_broadcast_shapes():
    k1 = np.arange(100, dtype=np.int32)
    k2 = (np.arange(100) % 7).astype(np.int32)
    idx = build_hash([k1, k2])
    q1 = np.arange(12, dtype=np.int32).reshape(3, 4)
    q2 = (np.arange(12) % 7).astype(np.int32).reshape(3, 4)
    got = _probe_host(idx, [k1, k2], [q1, q2])
    assert got.shape == (3, 4)
    ok = (np.arange(12) % 7) == (np.arange(12) % 7)  # by construction all hit
    assert (got.ravel()[ok] == np.arange(12)[ok]).all()


def test_range_index_matches_searchsorted():
    rng = np.random.default_rng(3)
    G, reps = 200, 6
    k = np.repeat(rng.choice(100000, G, replace=False), reps)
    k = np.sort(k).astype(np.int32)
    ri = build_range_hash(k)
    assert ri.max_run == reps
    arrays = {
        "gk": jnp.asarray(ri.gk),
        "glo": jnp.asarray(ri.glo), "ghi": jnp.asarray(ri.ghi),
        "off": jnp.asarray(ri.index.off), "rows": jnp.asarray(ri.index.rows),
    }
    # probe every distinct key + some misses
    q = np.concatenate([ri.gk, np.asarray([123456789, -7], np.int32)])
    lo, hi = probe_range(arrays, ri.index.cap, ri.index.n, jnp.asarray(q))
    lo, hi = np.asarray(lo), np.asarray(hi)
    for i in range(len(ri.gk)):
        assert lo[i] == np.searchsorted(k, q[i], "left")
        assert hi[i] == np.searchsorted(k, q[i], "right")
    assert (lo[-2:] == 0).all() and (hi[-2:] == 0).all()


def test_range_index_empty():
    ri = build_range_hash(np.zeros(0, np.int32))
    assert ri.max_run == 0
    arrays = {
        "gk": jnp.asarray(np.zeros(1, np.int32)),
        "glo": jnp.asarray(np.zeros(1, np.int32)),
        "ghi": jnp.asarray(np.zeros(1, np.int32)),
        "off": jnp.asarray(ri.index.off), "rows": jnp.asarray(ri.index.rows),
    }
    lo, hi = probe_range(arrays, ri.index.cap, ri.index.n,
                         jnp.asarray([3], dtype=jnp.int32))
    assert int(lo[0]) == 0 and int(hi[0]) == 0


def test_probe_block_matches_probe_rows():
    """The block-slice probe must find exactly the rows the scattered
    probe finds, across random tables and query mixes."""
    import numpy as np

    from gochugaru_tpu.engine.hash import (
        build_hash, interleave_buckets, probe_block, probe_rows,
    )

    rng = np.random.default_rng(0)
    for n in (0, 1, 7, 500):
        k1 = rng.integers(0, 200, n).astype(np.int32)
        k2 = rng.integers(0, 50, n).astype(np.int32)
        payload = np.arange(n, dtype=np.int32)
        h = build_hash([k1, k2])
        tbl = interleave_buckets(h, [k1, k2, payload])
        q1 = rng.integers(-1, 220, 64).astype(np.int32)
        q2 = rng.integers(-1, 60, 64).astype(np.int32)
        import jax.numpy as jnp

        blk = np.asarray(
            probe_block(
                jnp.asarray(h.off), jnp.asarray(tbl), max(h.cap, 1),
                (jnp.asarray(q1), jnp.asarray(q2)),
            )
        )
        hit = (
            (blk[..., 0] == q1[:, None])
            & (blk[..., 1] == q2[:, None])
            & (q1 >= 0)[:, None]
            & (q2 >= 0)[:, None]
        )
        got = np.where(hit.any(1), blk[..., 2].max(1, initial=-1, where=hit), -1)
        if n == 0:
            assert (got == -1).all()
            continue
        row = np.asarray(
            probe_rows(h.off, h.rows, (k1, k2), (q1, q2), max(h.cap, 1), h.n)
        )
        want = np.where(row >= 0, payload[np.clip(row, 0, max(n - 1, 0))], -1)
        np.testing.assert_array_equal(got, want)


def test_slice_blocks_never_shifts_within_pad():
    """A slice starting at any real offset must return exactly the rows
    at [start, start+cap) — the pad guarantees no clamp shift."""
    import numpy as np

    from gochugaru_tpu.engine.hash import interleave_rows, slice_blocks

    vals = np.arange(100, dtype=np.int32)
    tbl = interleave_rows([vals, vals * 2], pad=16)
    starts = np.asarray([0, 1, 57, 99, 100], np.int32)
    import jax.numpy as jnp

    blk = np.asarray(slice_blocks(jnp.asarray(tbl), jnp.asarray(starts), 8))
    for i, s in enumerate(starts):
        for j in range(8):
            want = s + j if s + j < 100 else -1
            assert blk[i, j, 0] == want, (s, j)


def test_stack_point_and_range_cover_all_rows():
    """Bucket-sharded stacking: every row lands on exactly one shard, at
    the local offset its (normalized) bucket table says."""
    import numpy as np

    from gochugaru_tpu.engine.hash import build_hash, mix32
    from gochugaru_tpu.engine.flat import _stack_point

    rng = np.random.default_rng(3)
    n, M = 300, 4
    k = rng.integers(0, 10_000, n).astype(np.int32)
    payload = np.arange(n, dtype=np.int32)
    h = build_hash([k], min_size=M)
    off, tbl = _stack_point(h, [k, payload], M)
    bpd = (off.shape[0] // M) - 1
    tbl3 = tbl.reshape(M, -1, 2)
    off2 = off.reshape(M, bpd + 1)
    seen = []
    for i in range(n):
        b = int(mix32([k[i : i + 1]])[0] & np.uint32(h.size - 1))
        s = b // bpd
        lo, hi = off2[s, b % bpd], off2[s, b % bpd + 1]
        rows = tbl3[s, lo:hi]
        match = rows[(rows[:, 0] == k[i]) & (rows[:, 1] == payload[i])]
        assert match.shape[0] == 1, i
        seen.append(int(match[0, 1]))
    assert sorted(seen) == list(range(n))


def test_slice_blocks_flat_gather_form_is_bitwise_the_dynamic_slice():
    """The TPU lowering of slice_blocks (cap·w flat 1-D gathers) never
    runs by default on the CPU suite: pin it bitwise against the
    dynamic_slice form — packed uint16 lanes and plain int32 rows,
    starts at both clamps — before it meets a chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gochugaru_tpu.engine import hash as H

    rng = np.random.default_rng(11)
    for dtype, w, cap in ((np.uint16, 3, 8), (np.uint16, 6, 4),
                          (np.int32, 4, 5), (np.int32, 1, 1)):
        rows = 1 << 10
        tbl = jnp.asarray(
            rng.integers(0, np.iinfo(dtype).max, (rows, w)).astype(dtype)
        )
        s = jnp.asarray(np.concatenate([
            rng.integers(0, rows - cap + 1, 200),
            [0, rows - cap],  # both ends of the clamp range
        ]).astype(np.int32))
        flat = jax.jit(H._slice_blocks_flat, static_argnums=2)(tbl, s, cap)
        dyn = jax.jit(H._slice_blocks_dynamic, static_argnums=2)(tbl, s, cap)
        assert flat.dtype == dyn.dtype and flat.shape == (202, cap, w)
        assert np.array_equal(np.asarray(flat), np.asarray(dyn))


@pytest.mark.parametrize("w", [2, 3])
def test_slice_rows_row_gather_is_bitwise_slice_blocks_of_the_split_columns(
    monkeypatch, w
):
    """A key + until row table (the fold's ``pfu_gku`` is 2 wide,
    ``csr_gdp`` 3) is read by slice_rows, one row gather a lane on TPU:
    forced here, it returns bit for bit what slice_blocks of each split
    1-wide column returns — in its CPU form and in its TPU flat form —
    at starts from 0 through mid-table to rows − cap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gochugaru_tpu.engine import hash as H

    rng = np.random.default_rng(40 + w)
    rows, cap = 1 << 10, 32
    cols = [
        rng.integers(-(2 ** 31), 2 ** 31 - 1, rows).astype(np.int32)
        for _ in range(w)
    ]
    tbl = jnp.asarray(np.stack(cols, axis=1))
    starts = jnp.asarray(np.concatenate([
        [0, 1, rows // 2, rows - cap - 1, rows - cap],
        rng.integers(0, rows - cap + 1, 59),
    ]).astype(np.int32).reshape(8, 8))

    def split(form_backend):
        monkeypatch.setattr(jax, "default_backend", lambda: form_backend)
        return [
            np.asarray(H.slice_blocks(jnp.asarray(c[:, None]), starts, cap))
            for c in cols
        ]

    cpu, flat = split("cpu"), split("tpu")
    called = []
    orig = H._slice_rows_gather
    monkeypatch.setattr(
        H, "_slice_rows_gather", lambda *a: called.append(1) or orig(*a)
    )
    got = np.asarray(H.slice_rows(tbl, starts, cap))
    assert called and got.shape == (8, 8, cap, w) and got.dtype == np.int32
    for j in range(w):
        assert np.array_equal(got[..., j], cpu[j][..., 0])
        assert np.array_equal(got[..., j], flat[j][..., 0])
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert np.array_equal(np.asarray(H.slice_rows(tbl, starts, cap)), got)


def test_slice_blocks_picks_the_flat_form_on_tpu(monkeypatch):
    """The branch keys off the default backend at trace time; a lattice
    of starts keeps its shape and out-of-range starts clamp either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gochugaru_tpu.engine import hash as H

    tbl = jnp.arange(64 * 3, dtype=jnp.int32).reshape(64, 3)
    starts = jnp.asarray([[0, 5, 70], [-3, 60, 63]], jnp.int32)
    want = np.asarray(H.slice_blocks(tbl, starts, 4))
    called = []
    orig = H._slice_blocks_flat
    monkeypatch.setattr(
        H, "_slice_blocks_flat",
        lambda *a: called.append(1) or orig(*a),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = np.asarray(H.slice_blocks(tbl, starts, 4))
    assert called and got.shape == (2, 3, 4, 3)
    assert np.array_equal(got, want)


def test_slice_blocks_flat_form_refuses_a_table_int32_cannot_address():
    """rows·w > 2³¹−1: an int64 offset would be narrowed to int32
    without jax_enable_x64 and wrap under promise_in_bounds — refused at
    trace time instead (no table is allocated: shapes only)."""
    import jax
    import jax.numpy as jnp
    import pytest

    from gochugaru_tpu.engine import hash as H

    tbl = jax.ShapeDtypeStruct((1 << 29, 6), jnp.uint16)
    s = jax.ShapeDtypeStruct((8,), jnp.int32)
    with pytest.raises(ValueError, match="int32 flat gather offsets"):
        jax.eval_shape(lambda t, s: H._slice_blocks_flat(t, s, 4), tbl, s)
    ok = jax.ShapeDtypeStruct((1 << 28, 6), jnp.uint16)
    assert jax.eval_shape(
        lambda t, s: H._slice_blocks_flat(t, s, 4), ok, s
    ).shape == (8, 4, 6)
