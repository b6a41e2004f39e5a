"""Caveated edges through the columnar imports, and checks that carry a
request context (BASELINE.md row 4's shape: benchmarks/bench4_caveats.py).

A seeded world of ~40k edges: every holder edge is written ``with
same_tenant`` and one of the call's stored contexts (``context_ids`` into
``contexts``); every check carries ``{tenant, tier}``.  The reference is
numpy over the edge lists and imports nothing of the program.  Stored
contexts come in three kinds, so that both sides of "the stored value
wins" are seen: ``{edge_tenant, tier: 2}``, ``{edge_tenant, tier: 0}``
and ``{edge_tenant}`` alone (the request's tier decides).
"""

from __future__ import annotations

import numpy as np
import pytest

from gochugaru_tpu import consistency, new_tpu_evaluator, rel
from gochugaru_tpu.client import with_latency_mode
from gochugaru_tpu.schema.compiler import SchemaValidationError
from gochugaru_tpu.store.store import RevisionToken
from gochugaru_tpu.utils import metrics
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import AlreadyExistsError
from gochugaru_tpu.utils.perf import gathered_bytes_model

SCHEMA = """
caveat same_tenant(tenant string, edge_tenant string, tier int) {
    tenant == edge_tenant && tier >= 1
}
definition user {}
definition org { relation admin: user }
definition item {
    relation org: org
    relation holder: user with same_tenant
    permission access = holder + org->admin
}
"""
PLAIN_SCHEMA = """
definition user {}
definition org { relation admin: user }
definition item {
    relation org: org
    relation holder: user
    permission access = holder + org->admin
}
"""
U, O, I, T = 2000, 40, 6000, 16
HOLDERS = 33_960  # + I org edges + O admins = 40,000
#: stored context ``k + T*kind``: kind 0 tier 2, kind 1 tier 0, kind 2 no tier
STORED = ([{"edge_tenant": f"t{k}", "tier": 2} for k in range(T)]
          + [{"edge_tenant": f"t{k}", "tier": 0} for k in range(T)]
          + [{"edge_tenant": f"t{k}"} for k in range(T)])
CS = consistency.full()
HOST = ("checks.oracle", "checks.fallback_conditional", "checks.fallback_overflow")
CONTEXT = ("engine.context_s.count", "engine.context_batches",
           "engine.query_contexts", "engine.context_checks",
           "engine.context_keyed_columns", "engine.context_repr_columns")


def build_world(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    key = np.unique(rng.integers(0, I * U, HOLDERS + HOLDERS // 8))
    key = rng.permutation(key)[:HOLDERS]
    kind = rng.choice(3, HOLDERS, p=[0.8, 0.1, 0.1])
    return {
        "org_admin": (np.arange(O), rng.integers(0, U, O)),
        "item_org": (np.arange(I), rng.integers(0, O, I)),
        "holder": (key // U, key % U, rng.integers(0, T, HOLDERS) + T * kind),
    }


def reference(w):
    """``check(items, users, tenants, tiers)`` from the edge lists: a
    holder whose stored tenant is the request's and whose tier (stored,
    else the request's) is at least 1, or the admin of the item's org."""
    hi, hu, hc = w["holder"]
    key = hi.astype(np.int64) * U + hu
    order = np.argsort(key)
    held, ctx = key[order], hc[order]
    edge_tenant, kind = ctx % T, ctx // T
    stored_tier = np.where(kind == 0, 2, np.where(kind == 1, 0, -1))
    org_of = np.empty(I, np.int64)
    org_of[w["item_org"][0]] = w["item_org"][1]
    admin_of = np.empty(O, np.int64)
    admin_of[w["org_admin"][0]] = w["org_admin"][1]

    def check(items, users, tenants, tiers):
        want = items.astype(np.int64) * U + users
        at = np.minimum(np.searchsorted(held, want), held.shape[0] - 1)
        tier = np.where(stored_tier[at] >= 0, stored_tier[at], tiers)
        holds = (held[at] == want) & (edge_tenant[at] == tenants) & (tier >= 1)
        return holds | (admin_of[org_of[items]] == users)

    return check


def probes(w, n: int, seed: int):
    """The quarter mix: uniform, a holder under its edge's tenant, a holder
    under another tenant, the item's org admin; request tier 0 or 2."""
    rng = np.random.default_rng(seed)
    hi, hu, hc = w["holder"]
    q = n // 4
    pick = rng.integers(0, hi.shape[0], 3 * q)
    items = np.concatenate([rng.integers(0, I, n - 3 * q), hi[pick[:2 * q]],
                            rng.integers(0, I, q)])
    admins = w["org_admin"][1][w["item_org"][1][items[-q:]]]
    users = np.concatenate([rng.integers(0, U, n - 3 * q), hu[pick[:2 * q]], admins])
    own = hc[pick[:2 * q]] % T
    tenants = np.concatenate([
        rng.integers(0, T, n - 3 * q), own[:q],
        (own[q:] + rng.integers(1, T, q)) % T, rng.integers(0, T, q)])
    tiers = rng.choice([0, 2], n)
    return items, users, tenants, tiers


def to_rels(items, users, tenants, tiers, drop=()):
    out = []
    for i, u, k, t in zip(items.tolist(), users.tolist(), tenants.tolist(),
                          tiers.tolist()):
        c = {n: v for n, v in (("tenant", f"t{k}"), ("tier", t)) if n not in drop}
        out.append(rel.must_from_triple(f"item:i{i}", "access", f"user:u{u}")
                   .with_caveat("", c))
    return out


def plain_columns(c, ctx, w):
    itn = c.store.interner
    ids = {t: itn.node_batch(t, [f"{p}{i}" for i in range(n)])
           for t, p, n in (("user", "u", U), ("org", "o", O), ("item", "i", I))}
    c.import_relationship_id_columns(
        ctx, resource_ids=ids["org"][w["org_admin"][0]], resource_relation="admin",
        subject_ids=ids["user"][w["org_admin"][1]])
    c.import_relationship_id_columns(
        ctx, resource_ids=ids["item"][w["item_org"][0]], resource_relation="org",
        subject_ids=ids["org"][w["item_org"][1]])
    return ids


def load(how: str, w, *, options=()):
    """A client holding ``w``: the holders through ``how`` (``ids``:
    ``import_relationship_id_columns``, ``strings``:
    ``import_relationship_columns``, ``objects``: ``import_relationships``
    of ``Relationship``s with caveat and stored context)."""
    c = new_tpu_evaluator(*options)
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    ids = plain_columns(c, ctx, w)
    hi, hu, hc = w["holder"]
    if how == "ids":
        c.import_relationship_id_columns(
            ctx, resource_ids=ids["item"][hi], resource_relation="holder",
            subject_ids=ids["user"][hu], caveat_name="same_tenant",
            context_ids=hc, contexts=STORED)
    elif how == "strings":
        c.import_relationship_columns(
            ctx, resource_type="item", resource_ids=[f"i{i}" for i in hi.tolist()],
            resource_relation="holder", subject_type="user",
            subject_ids=[f"u{u}" for u in hu.tolist()], caveat_name="same_tenant",
            context_ids=hc, contexts=STORED)
    else:
        c.import_relationships(ctx, (
            rel.must_from_triple(f"item:i{i}", "holder", f"user:u{u}")
            .with_caveat("same_tenant", STORED[k])
            for i, u, k in zip(hi.tolist(), hu.tolist(), hc.tolist())))
    return c


def exported(c) -> list:
    return sorted(str(r) for r in c.export_relationships(
        background(), RevisionToken(c.store.head_revision)))


def counters(names) -> list:
    snap = metrics.default.snapshot()
    return [snap.get(k, 0) for k in names]


@pytest.fixture(scope="module")
def world():
    return build_world(36)


@pytest.fixture(scope="module")
def clients(world):
    return {}


def client_of(clients, world, how: str):
    if how not in clients:
        clients[how] = load(how, world, options=(with_latency_mode(),))
    return clients[how]


@pytest.mark.parametrize("how", ["ids", "strings"])
def test_columnar_caveat_import_equals_the_object_import(clients, world, how):
    cols, objs = client_of(clients, world, how), client_of(clients, world, "objects")
    assert exported(cols) == exported(objs)
    assert len(exported(cols)) == HOLDERS + I + O
    columns = probes(world, 512, 1)
    rels = to_rels(*columns)
    assert cols.check(background(), CS, *rels) == objs.check(background(), CS, *rels)


@pytest.mark.parametrize("entry", ["client.check", "serving.check"])
def test_checks_with_request_context_equal_the_reference(clients, world, entry):
    c = client_of(clients, world, "ids")
    check = reference(world)
    if entry == "client.check":
        sizes = [2048, 2048]
        call = lambda rels: c.check(background(), CS, *rels)
    else:
        sizes = list(range(1, 17))
        handle = c.with_serving(cs=consistency.min_latency())
        call = lambda rels: handle.check(background(), *rels)
    columns = probes(world, sum(sizes), 2)
    want = check(*columns)
    assert want.any() and not want.all()
    rels = to_rels(*columns)
    host = counters(HOST)
    definite = counters(["checks.device_definite"])[0]
    got, at = [], 0
    try:
        for n in sizes:
            got += call(rels[at:at + n])
            at += n
    finally:
        if entry == "serving.check":
            handle.close()
    assert got == want.tolist()
    assert counters(HOST) == host
    assert counters(["checks.device_definite"])[0] - definite == len(rels)


@pytest.mark.parametrize("stored_kind,request_tier,granted", [
    (0, 0, True),   # stored tier 2 wins over a request's 0
    (1, 2, False),  # stored tier 0 wins over a request's 2
    (2, 2, True),   # no stored tier: the request's decides
    (2, 0, False),
])
def test_the_stored_value_wins(clients, world, stored_kind, request_tier, granted):
    c = client_of(clients, world, "ids")
    hi, hu, hc = world["holder"]
    at = np.flatnonzero(hc // T == stored_kind)[:64]
    item, user = hi[at], hu[at]
    admin = world["org_admin"][1][world["item_org"][1][item]]
    at, item, user = at[admin != user], item[admin != user], user[admin != user]
    got = c.check(background(), CS, *to_rels(
        item, user, hc[at] % T, np.full(at.shape[0], request_tier)))
    assert got == [granted] * at.shape[0]


def test_a_context_without_tenant_is_resolved_on_the_host(clients, world):
    c = client_of(clients, world, "ids")
    items, users, tenants, tiers = probes(world, 256, 3)
    before = counters(["checks.fallback_conditional"])[0]
    got = c.check(background(), CS, *to_rels(items, users, tenants, tiers,
                                             drop=("tenant",)))
    # the holder grant needs ``tenant``: only the org admins are granted
    admin = world["org_admin"][1][world["item_org"][1][items]] == users
    assert got == admin.tolist()
    assert counters(["checks.fallback_conditional"])[0] > before


REFUSED = {
    "unknown caveat": dict(caveat_name="no_such_caveat", context_ids=[0, 0],
                           contexts=STORED[:1]),
    "relation without the caveat": dict(resource_relation="org", subject="org",
                                        caveat_name="same_tenant",
                                        context_ids=[0, 0], contexts=STORED[:1]),
    "context id past the list": dict(caveat_name="same_tenant",
                                     context_ids=[0, 1], contexts=STORED[:1]),
    "context id below -1": dict(caveat_name="same_tenant", context_ids=[-2, 0],
                                contexts=STORED[:1]),
    "undeclared parameter": dict(caveat_name="same_tenant", context_ids=[0, 0],
                                 contexts=[{"edge_tenant": "t0", "colour": 1}]),
}


@pytest.mark.parametrize("api", ["ids", "strings"])
@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_bad_caveat_import_is_refused_with_nothing_applied(case, api):
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    kw = dict(REFUSED[case])
    relation, stype = kw.pop("resource_relation", "holder"), kw.pop("subject", "user")
    head, pool = c.store.head_revision, len(c.store._base_contexts)
    with pytest.raises((SchemaValidationError, ValueError)):
        if api == "ids":
            itn = c.store.interner
            c.import_relationship_id_columns(
                ctx, resource_ids=itn.node_batch("item", ["a", "b"]),
                resource_relation=relation,
                subject_ids=itn.node_batch(stype, ["x", "y"]), **kw)
        else:
            c.import_relationship_columns(
                ctx, resource_type="item", resource_ids=["a", "b"],
                resource_relation=relation, subject_type=stype,
                subject_ids=["x", "y"], **kw)
    assert c.store.head_revision == head
    assert len(c.store._base_contexts) == pool


@pytest.mark.parametrize("api", ["ids", "strings"])
def test_duplicates_refuse_in_the_store_and_touch_through_the_client(api):
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    itn = c.store.interner

    def put(store: bool, ids, **kw):
        if api == "ids":
            args = dict(resource_ids=itn.node_batch("item", ["a", "b"]),
                        resource_relation="holder",
                        subject_ids=itn.node_batch("user", ["x", "y"]),
                        caveat_name="same_tenant", context_ids=ids, **kw)
            if store:
                return c.store.import_interned_columns(**args)
            return c.import_relationship_id_columns(ctx, **args)
        args = dict(resource_type="item", resource_ids=["a", "b"],
                    resource_relation="holder", subject_type="user",
                    subject_ids=["x", "y"], caveat_name="same_tenant",
                    context_ids=ids, **kw)
        if store:
            return c.store.import_columns(**args)
        return c.import_relationship_columns(ctx, **args)

    put(False, [0, 1], contexts=STORED[:2])
    head = c.store.head_revision
    with pytest.raises(AlreadyExistsError):
        put(True, [1, 0], contexts=STORED[:2])
    assert c.store.head_revision == head
    put(False, [1, 0], contexts=STORED[:2])  # touch: the new contexts win
    assert exported(c) == [
        'item:a#holder@user:x[same_tenant:{"edge_tenant":"t1","tier":2}]',
        'item:b#holder@user:y[same_tenant:{"edge_tenant":"t0","tier":2}]',
    ]
    probe = lambda i, u, t: rel.must_from_triple(f"item:{i}", "access", f"user:{u}") \
        .with_caveat("", {"tenant": t, "tier": 2})
    assert c.check(ctx, CS, probe("a", "x", "t1"), probe("a", "x", "t0"),
                   probe("b", "y", "t0")) == [True, False, True]
    # one pool entry a distinct context, however often it is imported
    assert len(c.store._base_contexts) == 2


@pytest.mark.parametrize("caveated", [True, False])
def test_the_context_timer_and_counters(clients, world, caveated):
    if caveated:
        c = client_of(clients, world, "ids")
    else:
        c = new_tpu_evaluator()
        c.write_schema(background(), PLAIN_SCHEMA)
        plain_columns(c, background(), world)
    items, users, tenants, tiers = probes(world, 300, 4)
    rels = to_rels(items, users, tenants, tiers)
    before = counters(CONTEXT)
    c.check(background(), CS, *rels)
    gained = [a - b for a, b in zip(counters(CONTEXT), before)]
    if not caveated:
        assert gained == [0] * len(CONTEXT)
        return
    distinct = len(set(zip(tenants.tolist(), tiers.tolist())))
    # two parameter columns, tenant and tier, both keyed by value
    assert gained == [1, 1, distinct, 300, 2, 0]


def bytes_world(caveated: bool):
    """A small world of the schema, prepared; its DeviceSnapshot."""
    w = build_world(5)
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA if caveated else PLAIN_SCHEMA)
    ids = plain_columns(c, ctx, w)
    hi, hu, hc = (a[:3000] for a in w["holder"])
    kw = dict(caveat_name="same_tenant", context_ids=hc, contexts=STORED) if caveated else {}
    c.import_relationship_id_columns(
        ctx, resource_ids=ids["item"][hi], resource_relation="holder",
        subject_ids=ids["user"][hu], **kw)
    c.check(ctx, CS, rel.must_from_triple("item:i0", "access", "user:u0"))
    (dsnap,) = c._dsnap_cache.values()
    return dsnap


def test_the_byte_model_of_a_caveat_free_snapshot_is_unchanged():
    model = gathered_bytes_model(bytes_world(False))
    # the parent commit's model of this snapshot, to the byte
    assert model.total == 136.0
    assert model.per_level == (44.0, 46.0, 46.0)
    assert model.per_table == {"eh_off": 18.0, "ehx": 48.0, "pfh_off": 6.0,
                               "pfx": 16.0, "arr_off": 12.0, "argx": 32.0,
                               "arx": 4.0}


def test_the_byte_model_charges_the_gates_of_a_caveated_snapshot():
    dsnap = bytes_world(True)
    model = gathered_bytes_model(dsnap)
    meta, arrs = dsnap.flat_meta, dsnap.arrays
    assert meta.e_hascav
    row = sum(arrs[k].shape[-1] * arrs[k].dtype.itemsize
              for k in ("ectx_vi", "ectx_vf", "ectx_pr", "ectx_host"))
    # one stored- and one request-context row a gated candidate lane
    assert model.per_table["ectx"] == model.per_table["qctx"] > 0
    assert model.per_table["ectx"] % row == 0
    assert model.total == sum(model.per_table.values()) == sum(model.per_level)
    # the gate lanes ride in the direct-edge rows: wider than without them
    plain = gathered_bytes_model(bytes_world(False))
    assert model.per_table["ehx"] > plain.per_table["ehx"]
