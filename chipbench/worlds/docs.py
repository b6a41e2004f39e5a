"""BASELINE config 3: Google-Docs nested groups, 5-hop recursive userset
rewrite.  World, probes (copied from chip_smoke.py, which ran them on the
chip at full scale) and the plain reference of ``document#view``.

Since PR 31 also the lookups of ``document#view`` (LookupResources of a
user, LookupSubjects of a document): their strata and their plain reference.

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
#: the folder levels whose viewers have one shape on every seed (0 and 1: the
#: root and its 16 children), see ``fix_top``
TOP_LEVELS = 2


def top_viewer(folder: int):
    """The shape of a top folder's viewer, the same on every seed: None for a
    user, else how many groups are nested below the group that views it
    (0 .. GROUP_DEPTH - 1).  Kinds alternate and the depths cycle, as a draw
    gives them on average (half groups, every depth as often); the root's is a
    group with two nested below it."""
    return (folder // 2 + 2) % GROUP_DEPTH if folder % 2 == 0 else None


def build_world(size: dict, seed: int) -> dict:
    """Edge lists per relation shape, as index pairs, exactly
    ``size['edges']`` edges in total: ``draw_world``'s, with the top of the
    folder tree given its fixed shape."""
    return fix_top(draw_world(size, seed), size, seed)


def fix_top(w: dict, size: dict, seed: int) -> dict:
    """Every document lies under the root and under one of its 16 children,
    so what views those 17 folders is paid by every lookup of a world: drawn,
    it made one world in ten send all its LookupSubjects over the fused
    rounds (the root's viewer a group with four nested below it: a third off
    the lookup cell's rate, PERF.md) and gave every other world its own
    number of rounds a lookup and its own level-1 answers.
    Here the *shape* of those viewers (user or group, and the group's place in
    its nesting chain) is ``top_viewer``'s on every seed; *who* they are still
    comes from the seed (stream ``[seed, 4]``).  One viewer edge a folder is
    swapped for another, so every count stays what ``draw_world`` made it."""
    U, G = size["users"], size["groups"]
    top = folders_of_level(TOP_LEVELS - 1, size["folders"])[1]
    rng = np.random.default_rng([seed, 4])
    chain = rng.integers(0, max(G // GROUP_DEPTH, 1), top) * GROUP_DEPTH
    user = rng.integers(0, U, top)
    shape = [top_viewer(f) for f in range(top)]
    by_group = np.array([d is not None for d in shape], bool)
    group = np.minimum(chain + np.array([GROUP_DEPTH - 1 - (d or 0) for d in shape]),
                       G - 1)
    f = np.arange(top)
    for key, mine, who in (("folder_group", by_group, group),
                           ("folder_user", ~by_group, user)):
        folders, viewers = w[key]
        below = folders >= top  # the lists stay sorted by folder
        w[key] = (np.concatenate([f[mine], folders[below]]),
                  np.concatenate([who[mine], viewers[below]]))
    return w


def draw_world(size: dict, seed: int) -> dict:
    """The world as the seed alone draws it: every folder's viewer, the top
    folders' too, a user or any group with equal chance."""
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


# -- lookups (PR 31): LookupResources of a user, LookupSubjects of a document ----

RESOURCES, SUBJECTS = 0, 1  # the two kinds of lookup
#: stratum -> (kind, folder level or None).  A folder stratum looks up a
#: user who views, or is a member (half of them through nesting) of the
#: group that views, a folder that many steps below the root (level 1:
#: indices 1-16, level 2: 17-272); ``documents`` draws its keys uniformly,
#: ``users`` uniformly inside each class of answer size, a class in its share.
#: ``make_lookups`` draws the strata in this order.
LOOKUP_STRATA = {
    "users": (RESOURCES, None),
    "level2_viewers": (RESOURCES, 2),
    "level1_viewers": (RESOURCES, 1),
    "documents": (SUBJECTS, None),
}


def folders_of_level(level: int, F: int):
    """[lo, hi) of the folder indices ``level`` steps below the root."""
    lo = (FOLDER_ARITY ** level - 1) // (FOLDER_ARITY - 1)
    hi = (FOLDER_ARITY ** (level + 1) - 1) // (FOLDER_ARITY - 1)
    return min(lo, F), min(hi, F)


def documents_below(w, size: dict) -> np.ndarray:
    """For each folder, the documents in it and in every folder below it.
    Built once per world."""
    below = w.get("_documents_below")
    if below is None:
        F = size["folders"]
        below = w["_documents_below"] = np.bincount(w["doc_folder"][1], minlength=F)
        for f in range(F - 1, 0, -1):  # a parent comes before its children
            below[(f - 1) // FOLDER_ARITY] += below[f]
    return below


def answer_bounds(w, size: dict) -> np.ndarray:
    """For each user, a bound on the documents it views, from counts alone:
    its direct documents, those of every group it is a member of (nested to
    any depth), and all the documents at or below each folder that it or
    such a group views — the size of its LookupResources answer where none
    of these overlap.  Built once per world."""
    bound = w.get("_answer_bounds")
    if bound is None:
        U, G = size["users"], size["groups"]
        below = documents_below(w, size)
        of_group = np.bincount(w["doc_group"][1], minlength=G)
        np.add.at(of_group, w["folder_group"][1], below[w["folder_group"][0]])
        bound = np.bincount(w["doc_user"][1], minlength=U)
        np.add.at(bound, w["folder_user"][1], below[w["folder_user"][0]])
        members = member_closure(pair_keys(*w["group_user"]),
                                 *w["group_group"], G)
        np.add.at(bound, members & 0xFFFFFFFF, of_group[members >> 32])
        w["_answer_bounds"] = bound
    return bound


def size_class(documents) -> np.ndarray:
    """The class of an answer of about that many documents: its power of two."""
    return np.log2(np.maximum(documents, 16)).astype(np.int64)


def spread_over_classes(classes: np.ndarray, n: int, rng) -> np.ndarray:
    """``n`` distinct indices into ``classes``, each class given its share of
    them (largest remainders first), drawn inside a class by ``rng``: a
    uniform draw's make-up without its luck."""
    values, counts = np.unique(classes, return_counts=True)
    exact = n * counts / counts.sum()
    take = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - take), kind="stable")
    take[order[:n - int(take.sum())]] += 1
    return np.concatenate([rng.choice(np.nonzero(classes == v)[0], t, replace=False)
                           for v, t in zip(values, take)])


def viewer_of(ix, rng, folders):
    """One user per folder who views it: its viewer, or a member (half of
    them through nesting) of the group that is its viewer."""
    by_group = ix["fv_group"][folders] >= 0
    u = np.where(by_group, 0, ix["fv_user"][folders])
    u[by_group] = member_of(ix, rng, ix["fv_group"][folders][by_group])
    return u


def make_lookups(w, size: dict, rng, strata: dict):
    """``sum(strata.values())`` lookups as (kinds, keys, stratum names): the
    key of a RESOURCES lookup is a user index, of a SUBJECTS lookup a
    document index.  Every seed gives the same strata, with other keys
    (distinct inside ``users`` and ``documents``), in another order."""
    unknown = set(strata) - set(LOOKUP_STRATA)
    if unknown:
        raise ValueError(f"no lookup stratum {sorted(unknown)}; this world has"
                         f" {sorted(LOOKUP_STRATA)}")
    ix = _probe_index(w, size)
    kinds, keys, names = [], [], []
    for name, (kind, level) in LOOKUP_STRATA.items():
        n = int(strata.get(name, 0))
        if kind == RESOURCES:
            # a user's answer is a few documents or most of a folder tree, and
            # a folder's half a thousand or ninety thousand (the tree's last
            # level is not full): drawn uniformly, the keys would change the
            # work with the seed.  So the users — all of them, or one viewer
            # of each folder of the level — come from every size class in the
            # class's own share, which is a seed's to within one
            users = (np.arange(size["users"]) if level is None else viewer_of(
                ix, rng, np.arange(*folders_of_level(level, size["folders"]))))
            classes = size_class(answer_bounds(w, size)[users])
            key = users[spread_over_classes(classes, n, rng)]
        else:
            key = rng.choice(size["docs"], n, replace=False)
        kinds.append(np.full(n, kind, np.int8))
        keys.append(np.asarray(key, np.int64))
        names += [name] * n
    order = rng.permutation(len(names))
    return (np.concatenate(kinds)[order], np.concatenate(keys)[order],
            np.array(names)[order])


def _by_row(rows, ids, n: int) -> list:
    """The distinct ids of each row, sorted."""
    keys = pair_keys(rows, ids)
    cut = np.searchsorted(keys >> 32, np.arange(n + 1))
    return [keys[a:b] & 0xFFFFFFFF for a, b in zip(cut[:-1], cut[1:])]


def lookup_reference(w, size: dict):
    """``answer(kind, keys)`` -> for each key its whole answer as a sorted
    array of indices, from the edge lists alone.  Documents of a user: the
    ones it views directly, those of every group it is a member of (nested
    to any depth), and the documents in every folder at or below a folder
    that it, or such a group, views.  Users of a document: the mirror, up
    the folder chain."""
    U, G, F, D = size["users"], size["groups"], size["folders"], size["docs"]
    members = member_closure(pair_keys(*w["group_user"]),
                             *w["group_group"], G)
    m_group, m_user = members >> 32, members & 0xFFFFFFFF
    (gd, gg), (ud, uu) = w["doc_group"], w["doc_user"]
    (gf, fg), (uf, fu) = w["folder_group"], w["folder_user"]
    (child, par), (fd, ff) = w["folder_parent"], w["doc_folder"]

    groups_of, members_of = CSR(m_user, m_group, U), CSR(m_group, m_user, G)
    docs_of_user, users_of_doc = CSR(uu, ud, U), CSR(ud, uu, D)
    docs_of_group, groups_of_doc = CSR(gg, gd, G), CSR(gd, gg, D)
    folders_of_user, users_of_folder = CSR(fu, uf, U), CSR(uf, fu, F)
    folders_of_group, groups_of_folder = CSR(fg, gf, G), CSR(gf, fg, F)
    children, parent = CSR(par, child, F), CSR(child, par, F)
    docs_in, folder_of = CSR(ff, fd, F), CSR(fd, ff, D)
    both = lambda pairs: tuple(np.concatenate(x) for x in zip(*pairs))

    def resources(users):
        rows = np.arange(users.shape[0])
        g_rows, groups = groups_of.expand(rows, users)
        found = [docs_of_user.expand(rows, users),
                 docs_of_group.expand(g_rows, groups)]
        f_rows, folders = both([folders_of_user.expand(rows, users),
                                folders_of_group.expand(g_rows, groups)])
        while f_rows.shape[0]:
            found.append(docs_in.expand(f_rows, folders))
            f_rows, folders = children.expand(f_rows, folders)
        return _by_row(*both(found), users.shape[0])

    def subjects(docs):
        rows = np.arange(docs.shape[0])
        found = [users_of_doc.expand(rows, docs)]
        via = [groups_of_doc.expand(rows, docs)]
        f_rows, folders = folder_of.expand(rows, docs)
        while f_rows.shape[0]:
            found.append(users_of_folder.expand(f_rows, folders))
            via.append(groups_of_folder.expand(f_rows, folders))
            f_rows, folders = parent.expand(f_rows, folders)
        found.append(members_of.expand(*both(via)))
        return _by_row(*both(found), docs.shape[0])

    def answer(kind: int, keys) -> list:
        keys = np.asarray(keys, np.int64)
        if not keys.shape[0]:
            return []
        return (resources if kind == RESOURCES else subjects)(keys)

    return answer
