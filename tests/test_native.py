"""Native ingest layer (C++ via ctypes): differential tests against the
pure-Python/numpy paths it replaces.  If the library can't build on a
platform, the whole module is skipped — the framework works identically
without it, just slower at scale."""

import numpy as np
import pytest

from gochugaru_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native ingest library not available"
)


def test_interner_matches_python_reference():
    from gochugaru_tpu.native.interner import NativeInterner
    from gochugaru_tpu.store.interner import Interner

    nat, ref = NativeInterner(), Interner()
    pairs = [
        ("user", "alice"), ("user", "bob"), ("doc", "alice"), ("user", "alice"),
        ("team", "eng"), ("doc", ""), ("user", "ünïcode-οκ"), ("team", "eng"),
    ]
    for t, i in pairs:
        assert nat.node(t, i) == ref.node(t, i)
    assert len(nat) == len(ref)
    assert nat.num_types == ref.num_types
    for n in range(len(ref)):
        assert nat.key_of(n) == ref.key_of(n)
    assert (nat.node_type_array() == ref.node_type_array()).all()
    assert nat.lookup("user", "bob") == ref.lookup("user", "bob")
    assert nat.lookup("user", "nope") == -1
    assert nat.lookup("ghost", "x") == -1


def test_interner_batch_equivalence_and_growth():
    from gochugaru_tpu.native.interner import NativeInterner

    it = NativeInterner()
    ids = [f"id{i}" for i in range(200_000)]  # forces several table growths
    nodes = it.node_batch("user", ids)
    assert nodes.dtype == np.int32
    assert len(np.unique(nodes)) == len(ids)
    # re-interning returns identical ids; singles agree with batch
    assert (it.node_batch("user", ids[:1000]) == nodes[:1000]).all()
    assert it.node("user", "id500") == nodes[500]
    found, _ = it.lookup_pairs(["user"] * 3, ["id0", "missing", "id199999"])
    assert found[0] == nodes[0] and found[1] == -1 and found[2] == nodes[-1]


def test_sorts_match_numpy():
    from gochugaru_tpu.native.sort import argsort1, lexsort2, lexsort4

    rng = np.random.default_rng(7)
    n = 100_000
    a = rng.integers(0, 50, n).astype(np.int32)
    b = rng.integers(-1, 40, n).astype(np.int32)
    c = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    d = rng.integers(0, 5, n).astype(np.int32)
    k = np.stack([a, b, c, d])
    got = k[:, lexsort4(a, b, c, d)]
    want = k[:, np.lexsort((d, c, b, a))]
    assert (got == want).all()
    got2 = k[:2, lexsort2(a, b)]
    want2 = k[:2, np.lexsort((b, a))]
    assert (got2 == want2).all()
    assert (a[argsort1(a)] == np.sort(a)).all()


def test_snapshot_build_native_vs_python_interner():
    """The same world through both interners produces equivalent snapshots
    (column-for-column after node-id translation is identity, since both
    assign ids in first-intern order)."""
    from gochugaru_tpu import rel
    from gochugaru_tpu.native.interner import NativeInterner
    from gochugaru_tpu.schema import compile_schema, parse_schema
    from gochugaru_tpu.store.interner import Interner
    from gochugaru_tpu.store.snapshot import build_snapshot

    schema = """
    definition user {}
    definition team { relation member: user | team#member }
    definition repo {
        relation owner: team
        relation reader: user
        permission read = reader + owner->member
    }
    """
    cs = compile_schema(parse_schema(schema))
    rels = [
        rel.must_from_tuple("team:eng#member", "user:alice"),
        rel.must_from_tuple("team:all#member", "team:eng#member"),
        rel.must_from_tuple("repo:core#owner", "team:all"),
        rel.must_from_tuple("repo:core#reader", "user:bob"),
    ]
    s_py = build_snapshot(1, cs, Interner(), rels, epoch_us=0)
    s_nat = build_snapshot(1, cs, NativeInterner(), rels, epoch_us=0)
    for col in ("e_rel", "e_res", "e_subj", "e_srel1", "ms_subj", "mp_subj",
                "ar_rel", "ar_res", "ar_child", "us_rel", "us_res"):
        assert (getattr(s_py, col) == getattr(s_nat, col)).all(), col
    assert (s_py.node_type == s_nat.node_type).all()


def test_store_uses_available_interner():
    from gochugaru_tpu.native.interner import make_interner
    from gochugaru_tpu.store.store import Store

    s = Store()
    it = make_interner()
    assert type(s.interner) is type(it)


def _interner(kind):
    from gochugaru_tpu.native.interner import NativeInterner
    from gochugaru_tpu.store.interner import Interner

    it = NativeInterner() if kind == "native" else Interner()
    for t, i in [("user", "alice"), ("user", "bob"), ("doc", "alice"),
                 ("team", "eng"), ("doc", ""), ("user", "ünïcode-οκ"),
                 ("user", "*"), ("doc", "d1")]:
        it.node(t, i)
    it.type_id("empty_type")  # a type with no node
    return it


LOOKUP_PAIRS_CASES = {
    "mixed_types": [("doc", "alice"), ("user", "alice"), ("team", "eng"),
                    ("user", "bob"), ("doc", "d1"), ("user", "alice")],
    "unknown_type": [("ghost", "alice"), ("user", "alice"), ("ghost", ""),
                     ("empty_type", "alice")],
    "unknown_id": [("user", "nope"), ("doc", "bob"), ("user", "bob"),
                   ("team", "alice")],
    "empty_id": [("doc", ""), ("user", ""), ("user", "bob")],
    "non_ascii_id": [("user", "ünïcode-οκ"), ("doc", "ünïcode-οκ"),
                     ("user", "alice"), ("user", "ünïcode")],
    "wildcard_id": [("user", "*"), ("doc", "*"), ("ghost", "*")],
    "empty_batch": [],
}


@pytest.mark.parametrize("case", sorted(LOOKUP_PAIRS_CASES))
@pytest.mark.parametrize("kind", ["native", "python"])
def test_lookup_pairs_equals_per_key_lookup(kind, case):
    """One call over mixed types answers what per-key ``lookup`` and
    ``type_lookup`` answer, key for key."""
    it = _interner(kind)
    pairs = LOOKUP_PAIRS_CASES[case]
    types, ids = [t for t, _ in pairs], [i for _, i in pairs]
    n0 = len(it)
    nodes, type_ids = it.lookup_pairs(types, ids)
    assert nodes.dtype == np.int32 and type_ids.dtype == np.int32
    assert nodes.shape == type_ids.shape == (len(pairs),)
    assert nodes.tolist() == [it.lookup(t, i) for t, i in pairs]
    assert type_ids.tolist() == [it.type_lookup(t) for t in types]
    assert len(it) == n0 and it.type_lookup("ghost") == -1  # interned nothing
    with pytest.raises(ValueError):
        it.lookup_pairs(types + ["user"], ids)


def test_lookup_pairs_native_matches_python_on_random_pairs():
    from gochugaru_tpu.native.interner import NativeInterner
    from gochugaru_tpu.store.interner import Interner

    rng = np.random.default_rng(11)
    names = ["user", "doc", "team", "folder", "ghost"]
    nat, ref = NativeInterner(), Interner()
    for t, i in zip(rng.integers(0, 4, 5_000), rng.integers(0, 3_000, 5_000)):
        assert nat.node(names[t], f"o{i}") == ref.node(names[t], f"o{i}")
    types = [names[t] for t in rng.integers(0, 5, 10_000)]
    ids = [f"o{i}" for i in rng.integers(0, 4_000, 10_000)]
    got, got_t = nat.lookup_pairs(types, ids)
    want, want_t = ref.lookup_pairs(types, ids)
    assert (got == want).all() and (got_t == want_t).all()
    assert (got >= 0).any() and (got < 0).any()
    # the locked half alone, on ids packed as the lowering's pull packs them
    buf, offsets = nat._pack(ids)
    assert (nat.lookup_packed(buf, offsets, got_t) == want).all()
    with pytest.raises(ValueError):
        nat.lookup_packed(buf, offsets, got_t[1:])
