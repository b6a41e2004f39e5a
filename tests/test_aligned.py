"""Differential tests for the bucket-ALIGNED table layout
(engine/hash.py build_aligned / probe_aligned, wired through
engine/flat.py put_block + the name-keyed pblock dispatch).

The aligned layout is the TPU-shaped probe (one row gather per site;
its rate on a TPU is not measured); it defaults on only when the
backend is TPU, so these tests force ``flat_aligned=True`` to exercise it on the
CPU suite, asserting bit-identical results against the oracle and
against the legacy layout.
"""

import random

import numpy as np
import pytest

from gochugaru_tpu.engine.hash import build_aligned, probe_aligned
from tests.test_flat_engine import (
    FEATURES,
    NOW,
    assert_sound_cascade,
    build_feature_world,
    world,
)


def _all_checks(rng, n_users=10, n_groups=5, n_folders=6, n_docs=10, k=160):
    from gochugaru_tpu import rel

    perms = [
        ("doc", "read"), ("doc", "audit"), ("doc", "reader"),
        ("folder", "view"), ("group", "member"),
    ]
    checks = []
    for _ in range(k):
        t, p = rng.choice(perms)
        rid = rng.randrange({"doc": n_docs, "folder": n_folders,
                             "group": n_groups}[t])
        u = rng.randrange(n_users)
        r = rel.must_from_triple(f"{t}:{t[0]}{rid}",
                                 p, f"user:u{u}")
        checks.append(r)
    return checks


def test_aligned_matches_oracle_and_legacy():
    rng = random.Random(7)
    rels = build_feature_world(rng)
    checks = _all_checks(rng)

    eng_a, ds_a, oracle = world(FEATURES, rels, flat_aligned=True)
    assert ds_a.flat_meta.aligned, "aligned layout did not engage"
    assert any(k.endswith("_al") for k in ds_a.arrays), "no _al arrays"
    assert_sound_cascade(eng_a, ds_a, oracle, checks)

    eng_l, ds_l, _ = world(FEATURES, rels, flat_aligned=False)
    assert not ds_l.flat_meta.aligned
    da, pa, ova = eng_a.check_batch(ds_a, checks, now_us=NOW)
    dl, pl, ovl = eng_l.check_batch(ds_l, checks, now_us=NOW)
    assert np.array_equal(np.asarray(da), np.asarray(dl))
    assert np.array_equal(np.asarray(pa), np.asarray(pl))
    assert np.array_equal(np.asarray(ova), np.asarray(ovl))


def test_aligned_survives_delta_chain():
    """Incremental prepares keep the aligned base tables resident; the
    delta overlays stay on the legacy replicated layout."""
    from gochugaru_tpu import rel

    rng = random.Random(11)
    rels = build_feature_world(rng)
    eng, ds, oracle = world(FEATURES, rels, flat_aligned=True)
    assert ds.flat_meta.aligned

    from gochugaru_tpu.engine.oracle import Oracle
    from gochugaru_tpu.store.delta import apply_delta

    adds = [
        rel.must_from_tuple("doc:d0#reader", "user:u9"),
        rel.must_from_tuple("doc:d1#banned", "user:u2"),
    ]
    rels2 = rels + adds
    snap2 = apply_delta(
        ds.snapshot, 2, adds, [], interner=ds.snapshot.interner
    )
    ds2 = eng.prepare(snap2, prev=ds)
    assert ds2.flat_meta.delta is not None, "delta path not taken"
    assert ds2.flat_meta.aligned, "aligned meta lost across delta"
    oracle2 = Oracle(eng.compiled, rels2, {}, now_us=NOW)
    checks = _all_checks(random.Random(3)) + adds
    assert_sound_cascade(eng, ds2, oracle2, checks)


def test_build_aligned_duplicate_tail_falls_back():
    """A full key duplicated past cap+spill capacity makes the aligned
    build refuse (returns None) instead of silently dropping rows."""
    n = 4000
    k1 = np.zeros(n, np.int32)  # one bucket
    k2 = np.zeros(n, np.int32)
    pay = np.arange(n, dtype=np.int32)
    assert build_aligned([k1, k2], [k1, k2, pay]) is None


def test_probe_aligned_roundtrip_with_spill():
    rng = np.random.default_rng(5)
    n = 50_000
    k1 = rng.integers(0, n // 3, n).astype(np.int32)
    k2 = rng.integers(0, 1 << 20, n).astype(np.int32)
    # one full key duplicated past the single-level cap forces the spill
    # level (the builder otherwise absorbs Poisson tails by widening the
    # primary rows — one gather beats two)
    k1[:20] = 7
    k2[:20] = 9
    pay = rng.integers(1, 1 << 30, n).astype(np.int32)
    ai = build_aligned([k1, k2], [k1, k2, pay])
    assert ai is not None and ai.spill is not None

    import jax.numpy as jnp

    qi = rng.integers(0, n, 2048)
    tbls = [jnp.asarray(t) for t, _ in ai.levels]
    blk = probe_aligned(
        tbls, ai.caps, ai.w,
        (jnp.asarray(k1[qi]), jnp.asarray(k2[qi])),
    )
    hit = (blk[..., 0] == k1[qi][:, None]) & (blk[..., 1] == k2[qi][:, None])
    assert bool(hit.any(axis=-1).all()), "an inserted key failed to probe"
    # a key that was never inserted must miss everywhere
    miss = probe_aligned(
        tbls, ai.caps, ai.w,
        (jnp.full(64, n + 7, jnp.int32), jnp.full(64, -2, jnp.int32)),
    )
    mh = (miss[..., 0] == (n + 7)) & (miss[..., 1] == -2)
    assert not bool(mh.any())
