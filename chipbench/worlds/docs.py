"""BASELINE config 3: Google-Docs nested groups, 5-hop recursive userset
rewrite.  World, probes (copied from chip_smoke.py, which ran them on the
chip at full scale) and the plain reference of ``document#view``.

Index space throughout: object i of a type is ``<prefix><i>``.
"""

from __future__ import annotations

import numpy as np

from refkit import CSR, any_by_row, has_pair, member_closure, pair_keys, unique_pairs

SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition folder {
    relation parent: folder
    relation viewer: user | group#member
    permission view = viewer + parent->view
}
definition document {
    relation folder: folder
    relation viewer: user | group#member
    permission view = viewer + folder->view
}
"""

#: (type, id prefix, key of its count in the configuration's sizes)
TYPES = (("user", "u", "users"), ("group", "g", "groups"),
         ("folder", "f", "folders"), ("document", "d", "docs"))
#: (edge list, resource type, relation, subject type, subject relation)
SHAPES = (
    ("group_group", "group", "member", "group", "member"),
    ("group_user", "group", "member", "user", ""),
    ("folder_parent", "folder", "parent", "folder", ""),
    ("folder_group", "folder", "viewer", "group", "member"),
    ("folder_user", "folder", "viewer", "user", ""),
    ("doc_folder", "document", "folder", "folder", ""),
    ("doc_group", "document", "viewer", "group", "member"),
    ("doc_user", "document", "viewer", "user", ""),
)
#: what a probe (resource index, subject index) asks
PROBE = {"resource": ("document", "d"), "permission": "view",
         "subject": ("user", "u")}
#: the edge list a stale reader would miss the end of (imported last)
NEWEST = "doc_user"

GROUP_DEPTH = 5  # nesting chains break every 5 groups
FOLDER_ARITY = 16
MEMBERS_PER_GROUP = 6


def build_world(size: dict, seed: int) -> dict:
    """Edge lists per relation shape, as index pairs, exactly
    ``size['edges']`` edges in total."""
    rng = np.random.default_rng(seed)
    U, G, F, D = size["users"], size["groups"], size["folders"], size["docs"]
    w = {}
    g = np.arange(G - 1)
    deep = g[(g % GROUP_DEPTH) != GROUP_DEPTH - 1]
    w["group_group"] = (deep, deep + 1)
    w["group_user"] = unique_pairs(
        np.repeat(np.arange(G), MEMBERS_PER_GROUP),
        rng.integers(0, U, G * MEMBERS_PER_GROUP),
    )
    f = np.arange(1, F)
    w["folder_parent"] = (f, (f - 1) // FOLDER_ARITY)
    by_group = rng.random(F) < 0.5
    w["folder_group"] = (np.nonzero(by_group)[0],
                         rng.integers(0, G, int(by_group.sum())))
    w["folder_user"] = (np.nonzero(~by_group)[0],
                        rng.integers(0, U, int((~by_group).sum())))
    w["doc_folder"] = (np.arange(D), rng.integers(0, F, D))
    base = sum(a.shape[0] for a, _ in w.values())
    # top up with group viewers spread evenly over the documents (per-
    # document userset fan-in stays within the engine's leaf cap), the
    # rest as direct viewers
    per_doc = max((size["edges"] - base - D // 5) // D, 0)
    w["doc_group"] = unique_pairs(
        np.repeat(np.arange(D), per_doc), rng.integers(0, G, D * per_doc)
    )
    n_direct = size["edges"] - base - w["doc_group"][0].shape[0]
    if n_direct < 0:
        raise ValueError("edge target below the world's fixed edges")
    dd, du = unique_pairs(
        rng.integers(0, D, n_direct + n_direct // 16 + 64),
        rng.integers(0, U, n_direct + n_direct // 16 + 64),
    )
    keep = rng.permutation(dd.shape[0])[:n_direct]
    if keep.shape[0] != n_direct:
        raise ValueError("could not draw enough distinct direct viewers")
    w["doc_user"] = (dd[keep], du[keep])
    return w


def _probe_index(w, size: dict) -> dict:
    """What make_probes looks up, built once per world."""
    ix = w.get("_probe_index")
    if ix is None:
        F, G = size["folders"], size["groups"]
        fv_group = np.full(F, -1, np.int64)
        fv_user = np.full(F, -1, np.int64)
        fv_group[w["folder_group"][0]] = w["folder_group"][1]
        fv_user[w["folder_user"][0]] = w["folder_user"][1]
        gu_g, gu_u = w["group_user"]  # sorted by group
        ix = w["_probe_index"] = {
            "fv_group": fv_group, "fv_user": fv_user,
            "gm_start": np.searchsorted(gu_g, np.arange(G + 1)),
            "gm_user": gu_u,
        }
    return ix


def member_of(ix, rng, groups):
    """One user per group in ``groups`` who is a member of it, half of
    them through a nested descendant (g ⊇ g+1 ⊇ … inside a chain)."""
    G = ix["gm_start"].shape[0] - 1
    room = (GROUP_DEPTH - 1) - (groups % GROUP_DEPTH)
    room = np.minimum(room, G - 1 - groups)
    hop = np.where(rng.random(groups.shape[0]) < 0.5,
                   (rng.random(groups.shape[0]) * (room + 1)).astype(np.int64),
                   0)
    g = groups + hop
    lo, hi = ix["gm_start"][g], ix["gm_start"][g + 1]
    pick = lo + (rng.random(g.shape[0]) * (hi - lo)).astype(np.int64)
    return ix["gm_user"][pick]


def make_probes(w, size: dict, rng, n: int):
    """``n`` (document, user) probes: a quarter uniform (mostly denied),
    a quarter direct viewers, a quarter members of a viewer group (half
    of those through nesting), a quarter viewers of an ancestor folder —
    so every hop of the 5-hop rewrite is exercised both ways."""
    ix = _probe_index(w, size)
    D, U = size["docs"], size["users"]
    q = n // 4
    docs = [rng.integers(0, D, n - 3 * q)]
    users = [rng.integers(0, U, n - 3 * q)]
    pick = rng.integers(0, w["doc_user"][0].shape[0], q)
    docs.append(w["doc_user"][0][pick])
    users.append(w["doc_user"][1][pick])
    pick = rng.integers(0, w["doc_group"][0].shape[0], q)
    docs.append(w["doc_group"][0][pick])
    users.append(member_of(ix, rng, w["doc_group"][1][pick]))
    d = rng.integers(0, D, q)
    anc = w["doc_folder"][1][d]
    for _ in range(4):  # climb 0..4 levels (roots stay put)
        up = (rng.random(q) < 0.5) & (anc > 0)
        anc = np.where(up, (anc - 1) // FOLDER_ARITY, anc)
    by_group = ix["fv_group"][anc] >= 0
    u = np.where(by_group, 0, ix["fv_user"][anc])
    u[by_group] = member_of(ix, rng, ix["fv_group"][anc][by_group])
    docs.append(d)
    users.append(u)
    order = rng.permutation(n)
    return np.concatenate(docs)[order], np.concatenate(users)[order]


def reference(w, size: dict):
    """``check(docs, users)`` → ``document#view`` for each pair, from the
    edge lists alone: viewer (direct or through group membership, nested to
    any depth) on the document or on any folder above it."""
    members = member_closure(pair_keys(*w["group_user"]),
                             *w["group_group"], size["groups"])
    doc_user = pair_keys(*w["doc_user"])
    doc_group = CSR(*w["doc_group"], size["docs"])
    folder_user = pair_keys(*w["folder_user"])
    folder_group = CSR(*w["folder_group"], size["folders"])
    parent = CSR(*w["folder_parent"], size["folders"])
    doc_folder = CSR(*w["doc_folder"], size["docs"])

    def check(docs, users) -> np.ndarray:
        n = docs.shape[0]

        def viewer(direct, groups_of, rows, nodes):
            hit = any_by_row(rows, has_pair(direct, nodes, users[rows]), n)
            r, g = groups_of.expand(rows, nodes)
            return hit | any_by_row(r, has_pair(members, g, users[r]), n)

        rows = np.arange(n)
        out = viewer(doc_user, doc_group, rows, docs)
        rows, at = doc_folder.expand(rows, docs)
        while rows.shape[0]:
            out |= viewer(folder_user, folder_group, rows, at)
            rows, at = parent.expand(rows, at)
        return out

    return check
