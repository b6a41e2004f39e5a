"""BASELINE config 4: multi-tenant SaaS with caveats.  Schema as
benchmarks/bench4_caveats.py:35-46; users, orgs, tenants and the stored tier
as that script sets them, items and edges cut to one chip's eighth of the
deployment (``configs/caveats12m.json``).  Every holder edge is written
``with same_tenant`` and a stored ``{edge_tenant, tier}``; every check
carries the caller's ``{tenant, tier}``, and where both name a parameter the
stored value wins (SpiceDB's caveat semantics).  World, probes and the plain
reference of ``item#access``; the generators are those of the tests'
fixture world (``tests/fixture/bench/worlds/caveats.py``), so a seed draws
the same world in both, and the loader is this world's own: the holders go
in through the columnar import with a caveat and a stored-context column.

Index space throughout: object i of a type is ``<prefix><i>``, tenant k is
``"t<k>"``.
"""

from __future__ import annotations

import numpy as np

from refkit import has_pair, pair_keys, unique_pairs

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

TYPES = (("user", "u", "users"), ("org", "o", "orgs"), ("item", "i", "items"))
#: ``item_holder`` is three columns (item, user, tenant); the tenant is the
#: index of the edge's stored context
SHAPES = (
    ("org_admin", "org", "admin", "user", ""),
    ("item_org", "item", "org", "org", ""),
    ("item_holder", "item", "holder", "user", ""),
)
PROBE = {"resource": ("item", "i"), "permission": "access",
         "subject": ("user", "u")}
NEWEST = "item_holder"
CAVEAT = "same_tenant"
TIER = 2  # stored with every holder edge and sent with every check


def build_world(size: dict, seed: int) -> dict:
    """Edge lists per relation shape, exactly ``size['edges']`` in total:
    one org an item, one admin an org, distinct holders fill the rest."""
    rng = np.random.default_rng(seed)
    U, O, I, T = size["users"], size["orgs"], size["items"], size["tenants"]
    w = {"org_admin": (np.arange(O), rng.integers(0, U, O)),
         "item_org": (np.arange(I), rng.integers(0, O, I))}
    n = size["edges"] - I - O
    items, users = unique_pairs(rng.integers(0, I, n + n // 16 + 64),
                                rng.integers(0, U, n + n // 16 + 64))
    keep = rng.permutation(items.shape[0])[:n]
    if keep.shape[0] != n:
        raise ValueError("could not draw enough distinct holders")
    w["item_holder"] = (items[keep], users[keep], rng.integers(0, T, n))
    return w


def load_edges(client, ctx, ids: dict, w: dict, size: dict) -> int:
    """Every edge list as id columns, one call each; the holders with the
    caveat and their tenant as the index into the ``{edge_tenant, tier}``
    contexts, one a tenant.  Returns the edges imported."""
    stored = [{"edge_tenant": f"t{k}", "tier": TIER} for k in range(size["tenants"])]
    edges = 0
    for key, rtype, relation, stype, srel in SHAPES:
        r, s, *tenant = w[key]
        caveat = dict(caveat_name=CAVEAT, context_ids=tenant[0],
                      contexts=stored) if tenant else {}
        client.import_relationship_id_columns(
            ctx(), resource_ids=ids[rtype][r], resource_relation=relation,
            subject_ids=ids[stype][s], subject_relation=srel, **caveat)
        edges += int(r.shape[0])
    return edges


def make_probes(w, size: dict, rng, n: int):
    """``n`` (item, user, tenant) probes: a quarter uniform (mostly denied),
    a quarter a holder under the edge's tenant (granted), a quarter a holder
    under another tenant (denied, but for an admin), a quarter the admin of
    the item's org under any tenant (granted)."""
    U, I, T = size["users"], size["items"], size["tenants"]
    hi, hu, hk = w["item_holder"]
    q = n // 4
    items = [rng.integers(0, I, n - 3 * q)]
    users = [rng.integers(0, U, n - 3 * q)]
    tenants = [rng.integers(0, T, n - 3 * q)]
    pick = rng.integers(0, hi.shape[0], q)
    items.append(hi[pick])
    users.append(hu[pick])
    tenants.append(hk[pick])
    pick = rng.integers(0, hi.shape[0], q)
    items.append(hi[pick])
    users.append(hu[pick])
    tenants.append((hk[pick] + rng.integers(1, T, q)) % T)
    i = rng.integers(0, I, q)
    items.append(i)
    users.append(w["org_admin"][1][w["item_org"][1][i]])
    tenants.append(rng.integers(0, T, q))
    order = rng.permutation(n)
    return tuple(np.concatenate(c)[order] for c in (items, users, tenants))


def probe_rels(items, users, tenants) -> list:
    """The check of each probe: the bare triple plus the request's context."""
    from gochugaru_tpu import rel

    sent = {k: {"tenant": f"t{k}", "tier": TIER} for k in set(tenants.tolist())}
    return [rel.must_from_triple(f"item:i{i}", "access", f"user:u{u}")
            .with_caveat("", sent[k])
            for i, u, k in zip(items.tolist(), users.tolist(), tenants.tolist())]


def reference(w, size: dict):
    """``check(items, users, tenants)`` → ``item#access`` for each probe, from
    the edge lists alone: a holder whose edge's tenant is the request's (the
    tier, 2 on both sides, passes ``tier >= 1``), or the admin of the item's
    org."""
    hi, hu, hk = w["item_holder"]
    key = np.asarray(hi, np.int64) << 32 | np.asarray(hu, np.int64)
    order = np.argsort(key)
    held, held_tenant = key[order], np.asarray(hk)[order]
    admin = pair_keys(*w["org_admin"])
    org_of = np.empty(size["items"], np.int64)
    org_of[w["item_org"][0]] = w["item_org"][1]

    def check(items, users, tenants) -> np.ndarray:
        want = np.asarray(items, np.int64) << 32 | np.asarray(users, np.int64)
        at = np.minimum(np.searchsorted(held, want), held.shape[0] - 1)
        out = (held[at] == want) & (held_tenant[at] == tenants) & (TIER >= 1)
        return out | has_pair(admin, org_of[items], users)

    return check
