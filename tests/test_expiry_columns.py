"""Expiring edges through the columnar imports (SpiceDB's ``use expiration``:
``relation member: user with expiration``).

``expirations`` is one int column of micros since the Unix epoch, 0 for
none, beside the id columns of ``Store.import_columns`` /
``import_interned_columns`` and the client's ``import_relationship_columns``
/ ``import_relationship_id_columns``.  The rows land exactly as the same
rows written as ``Relationship.with_expiration`` objects; a relation whose
subject is not written ``with expiration`` refuses an expiring row, one
written only ``with expiration`` refuses a row without one, and a malformed
column refuses the call, each with nothing applied.  A row already expired
is stored, exported by no export and granted by no check.
"""

from __future__ import annotations

import datetime as dt
import time

import numpy as np
import pytest

from gochugaru_tpu import consistency, new_tpu_evaluator, rel
from gochugaru_tpu.rel.relationship import expiration_micros
from gochugaru_tpu.schema.compiler import SchemaValidationError
from gochugaru_tpu.store.store import RevisionToken
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background

SCHEMA = """
use expiration

definition user {}
definition team { relation member: user with expiration }
definition repo {
    relation maintainer: user | team#member with expiration
    relation reader: user with expiration
    relation owner: user
    permission read = reader + maintainer + owner
}
"""
CS = consistency.full()
HOUR_US = 3600 * 1_000_000
NOW_US = time.time_ns() // 1000
N = 12_000  # readers: at least the store's columnar-import floor


def readers(seed: int):
    """``N`` distinct (repo, user) readers and their expiries: a hundredth
    already expired, the rest live for a day or more, a tenth of them
    micros off a whole second."""
    rng = np.random.default_rng(seed)
    key = rng.permutation(np.unique(rng.integers(0, 4000 * 900, N + N // 4)))[:N]
    exp = NOW_US + rng.integers(24 * HOUR_US, 90 * 24 * HOUR_US, N)
    exp[rng.random(N) < 0.1] += 123_457
    exp[:N // 100] = NOW_US - rng.integers(HOUR_US, 24 * HOUR_US, N // 100)
    return key // 900, key % 900, exp


def as_datetime(exp_us: int) -> dt.datetime:
    return dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc) + dt.timedelta(
        microseconds=int(exp_us))


def load(how: str, seed: int):
    """A client holding the readers: ``ids`` / ``strings`` through the
    client's columnar calls, ``store-ids`` / ``store-strings`` through the
    store's, ``objects`` as ``Relationship``s with their expiration."""
    repos, users, exp = readers(seed)
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    itn = c.store.interner
    if how.endswith("ids"):
        kw = dict(resource_ids=itn.node_batch("repo", [f"r{i}" for i in repos.tolist()]),
                  resource_relation="reader",
                  subject_ids=itn.node_batch("user", [f"u{i}" for i in users.tolist()]),
                  expirations=exp)
        if how.startswith("store"):
            c.store.import_interned_columns(**kw)
        else:
            c.import_relationship_id_columns(ctx, **kw)
    elif how.endswith("strings"):
        kw = dict(resource_type="repo", resource_ids=[f"r{i}" for i in repos.tolist()],
                  resource_relation="reader", subject_type="user",
                  subject_ids=[f"u{i}" for i in users.tolist()], expirations=exp)
        if how.startswith("store"):
            c.store.import_columns(**kw)
        else:
            c.import_relationship_columns(ctx, **kw)
    else:
        c.import_relationships(ctx, (
            rel.must_from_triple(f"repo:r{r}", "reader", f"user:u{u}")
            .with_expiration(as_datetime(e))
            for r, u, e in zip(repos.tolist(), users.tolist(), exp.tolist())))
    return c


def stored_rows(snap) -> dict:
    """Every row the snapshot stores, expired or not: its key → (expiry
    micros, epoch-relative expiry seconds)."""
    out = {}
    at = 0
    for chunk in snap.decode_columns(np.arange(snap.num_edges)):
        for i in range(len(chunk["resource_ids"])):
            key = (chunk["resource_ids"][i], chunk["resource_relations"][i],
                   chunk["subject_ids"][i])
            out[key] = (chunk["expirations_us"][i], int(snap.e_exp[at]))
            at += 1
    return out


@pytest.mark.parametrize("how", ["ids", "strings", "store-ids", "store-strings"])
def test_columnar_expiries_are_the_object_paths(how, monkeypatch):
    cols, objs = load(how, 1), load("objects", 1)
    # both snapshots built at one instant: the same epoch
    monkeypatch.setattr(time, "time", lambda: NOW_US / 1e6 + 17.25)
    snap = cols.store.snapshot_for(CS)
    assert stored_rows(snap) == stored_rows(objs.store.snapshot_for(CS))
    monkeypatch.undo()
    # the store keeps the expired rows; the epoch-relative column is the
    # ceiling of each expiry's seconds since the snapshot's epoch
    assert np.count_nonzero(snap.e_exp_us) == N
    assert np.count_nonzero(snap.e_exp < 0) == N // 100
    want = -(-(snap.e_exp_us - snap.epoch_us) // 1_000_000)
    assert np.array_equal(snap.e_exp, np.where(want == 0, -1, want))
    exported = list(cols.export_relationships(
        background(), RevisionToken(cols.store.head_revision)))
    assert len(exported) == N - N // 100
    assert all(expiration_micros(r.expiration) > NOW_US for r in exported)


def test_an_import_counts_its_expiring_rows():
    before = metrics.default.counter("store.expiring_rows")
    load("ids", 2)
    assert metrics.default.counter("store.expiring_rows") - before == N
    c = new_tpu_evaluator()
    c.write_schema(background(), SCHEMA)
    c.import_relationship_columns(
        background(), resource_type="repo", resource_ids=["a", "b"],
        resource_relation="owner", subject_type="user", subject_ids=["x", "y"])
    assert metrics.default.counter("store.expiring_rows") - before == N


LATER = NOW_US + 48 * HOUR_US
REFUSED = {
    "an expiry on a relation without the trait":
        dict(relation="owner", expirations=[LATER, LATER]),
    "one expiring row on a relation without the trait":
        dict(relation="owner", expirations=[0, LATER]),
    "an expiry on the subject type the trait is not written on":
        dict(relation="maintainer", expirations=[LATER, LATER]),
    "no expiry on a relation written only with the trait":
        dict(relation="reader", expirations=[0, 0]),
    "one row without an expiry on a relation written only with the trait":
        dict(relation="reader", expirations=[LATER, 0]),
    "no expirations column on a relation written only with the trait":
        dict(relation="reader"),
    "a userset without an expiry where its trait is written":
        dict(relation="maintainer", subject=("team", "member"), expirations=[0, LATER]),
    "a column of the wrong length":
        dict(relation="reader", expirations=[LATER]),
    "a column of floats":
        dict(relation="reader", expirations=[float(LATER), float(LATER)]),
    "a negative expiry":
        dict(relation="reader", expirations=[LATER, -1]),
}


@pytest.mark.parametrize("api", ["ids", "strings", "store-ids", "store-strings"])
@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_bad_expiry_import_is_refused_with_nothing_applied(case, api):
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    kw = dict(REFUSED[case])
    relation = kw.pop("relation")
    stype, srel = kw.pop("subject", ("user", ""))
    itn = c.store.interner
    head = c.store.head_revision
    expiring = metrics.default.counter("store.expiring_rows")
    with pytest.raises((SchemaValidationError, ValueError)):
        if api.endswith("ids"):
            call = (c.store.import_interned_columns if api.startswith("store")
                    else lambda **k: c.import_relationship_id_columns(ctx, **k))
            call(resource_ids=itn.node_batch("repo", ["a", "b"]),
                 resource_relation=relation,
                 subject_ids=itn.node_batch(stype, ["x", "y"]),
                 subject_relation=srel, **kw)
        else:
            call = (c.store.import_columns if api.startswith("store")
                    else lambda **k: c.import_relationship_columns(ctx, **k))
            call(resource_type="repo", resource_ids=["a", "b"],
                 resource_relation=relation, subject_type=stype,
                 subject_ids=["x", "y"], subject_relation=srel, **kw)
    assert c.store.head_revision == head
    assert c.store._segments == [] and not c.store._live
    assert metrics.default.counter("store.expiring_rows") == expiring


@pytest.mark.parametrize("api", ["ids", "strings"])
def test_the_trait_admits_what_it_names(api):
    """``user | team#member with expiration``: a plain user row and an
    expiring team row go in, in one call where the ids allow it."""
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    itn = c.store.interner
    if api == "ids":
        c.import_relationship_id_columns(
            ctx, resource_ids=itn.node_batch("team", ["t"]),
            resource_relation="member", subject_ids=itn.node_batch("user", ["x"]),
            expirations=[LATER])
        c.import_relationship_id_columns(
            ctx, resource_ids=itn.node_batch("repo", ["a", "b"]),
            resource_relation="maintainer",
            subject_ids=itn.node_batch("user", ["y", "z"]))
        c.import_relationship_id_columns(
            ctx, resource_ids=itn.node_batch("repo", ["c"]),
            resource_relation="maintainer", subject_ids=itn.node_batch("team", ["t"]),
            subject_relation="member", expirations=np.array([LATER]))
    else:
        c.import_relationship_columns(
            ctx, resource_type="team", resource_ids=["t"], resource_relation="member",
            subject_type="user", subject_ids=["x"], expirations=[LATER])
        c.import_relationship_columns(
            ctx, resource_type="repo", resource_ids=["a", "b"],
            resource_relation="maintainer", subject_type="user",
            subject_ids=["y", "z"], expirations=[0, 0])
        c.import_relationship_columns(
            ctx, resource_type="repo", resource_ids=["c"], resource_relation="maintainer",
            subject_type="team", subject_ids=["t"], subject_relation="member",
            expirations=[LATER])
    check = lambda r, u: rel.must_from_triple(f"repo:{r}", "read", f"user:{u}")
    assert c.check(ctx, CS, check("a", "y"), check("c", "x"), check("c", "y")) == [
        True, True, False]


def test_an_export_round_trip_keeps_the_expiry_and_drops_the_expired():
    c = load("ids", 3)
    ctx = background()
    repos, users, exp = readers(3)
    rev = RevisionToken(c.store.head_revision)
    chunks = list(c.export_relationship_id_columns(ctx, rev))
    got = np.concatenate([ch["expirations"] for ch in chunks])
    assert sorted(got.tolist()) == sorted(exp[exp > NOW_US].tolist())
    assert all(ch["expirations"].dtype == np.int64 for ch in chunks)
    # the chunks go back in as they came out (a TOUCH over the same rows)
    for ch in chunks:
        c.import_relationship_id_columns(
            ctx, resource_ids=ch["res"], resource_relation=ch["resource_relation"],
            subject_ids=ch["subj"], subject_relation=ch["subject_relation"],
            expirations=ch["expirations"])
    again = list(c.export_relationship_id_columns(
        ctx, RevisionToken(c.store.head_revision)))
    assert sorted(np.concatenate([ch["expirations"] for ch in again]).tolist()) == \
        sorted(got.tolist())
    # the expired rows are stored still, and grant nothing
    snap = c.store.snapshot_for(CS)
    assert np.count_nonzero((snap.e_exp_us > 0) & (snap.e_exp_us <= NOW_US)) == N // 100
    gone = [rel.must_from_triple(f"repo:r{r}", "read", f"user:u{u}")
            for r, u in zip(repos[:N // 100].tolist(), users[:N // 100].tolist())]
    assert c.check(ctx, CS, *gone) == [False] * len(gone)
    live = [rel.must_from_triple(f"repo:r{r}", "read", f"user:u{u}")
            for r, u in zip(repos[-50:].tolist(), users[-50:].tolist())]
    assert c.check(ctx, CS, *live) == [True] * 50
