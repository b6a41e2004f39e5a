"""BASELINE config 2: GitHub-style RBAC, 2-hop org→team→repo.  Schema as
bench.py:62-76; the source's ratios (repos:teams:orgs = 1000:10:1, 4 edges
a repo, 8 an org, 100 members a team), scaled up to a deployment.  World,
probes and the plain reference of ``repo#read``.

Index space throughout: object i of a type is ``<prefix><i>``.
"""

from __future__ import annotations

import numpy as np

from refkit import CSR, any_by_row, has_pair, pair_keys, unique_pairs

SCHEMA = """
definition user {}
definition team { relation member: user }
definition org {
    relation admin: user
    relation member: user | team#member
}
definition repo {
    relation org: org
    relation maintainer: user | team#member
    relation reader: user
    permission admin = org->admin + maintainer
    permission read = reader + admin + org->member
}
"""

TYPES = (("user", "u", "users"), ("team", "t", "teams"),
         ("org", "o", "orgs"), ("repo", "r", "repos"))
SHAPES = (
    ("team_user", "team", "member", "user", ""),
    ("org_admin", "org", "admin", "user", ""),
    ("org_team", "org", "member", "team", "member"),
    ("org_user", "org", "member", "user", ""),
    ("repo_org", "repo", "org", "org", ""),
    ("repo_team", "repo", "maintainer", "team", "member"),
    ("repo_reader", "repo", "reader", "user", ""),
)
PROBE = {"resource": ("repo", "r"), "permission": "read",
         "subject": ("user", "u")}
NEWEST = "repo_reader"

ORG_TEAMS = 2  # team usersets among an org's members
ORG_USERS = 5  # direct members of an org


def _distinct_draws(rng, n_src: int, per: int, n_dst: int):
    """``per`` draws of a destination for each source, duplicates dropped."""
    return unique_pairs(np.repeat(np.arange(n_src), per),
                        rng.integers(0, n_dst, n_src * per))


def build_world(size: dict, seed: int) -> dict:
    """Edge lists per relation shape, exactly ``size['edges']`` in total:
    readers fill what the fixed shapes leave."""
    rng = np.random.default_rng(seed)
    U, T, O, R = size["users"], size["teams"], size["orgs"], size["repos"]
    w = {}
    w["team_user"] = _distinct_draws(rng, T, size["team_members"], U)
    w["org_admin"] = (np.arange(O), rng.integers(0, U, O))
    w["org_team"] = _distinct_draws(rng, O, ORG_TEAMS, T)
    w["org_user"] = _distinct_draws(rng, O, ORG_USERS, U)
    w["repo_org"] = (np.arange(R), rng.integers(0, O, R))
    w["repo_team"] = (np.arange(R), rng.integers(0, T, R))
    n_readers = size["edges"] - sum(a.shape[0] for a, _ in w.values())
    if n_readers < 0:
        raise ValueError("edge target below the world's fixed edges")
    rr, ru = unique_pairs(
        rng.integers(0, R, n_readers + n_readers // 16 + 64),
        rng.integers(0, U, n_readers + n_readers // 16 + 64),
    )
    keep = rng.permutation(rr.shape[0])[:n_readers]
    if keep.shape[0] != n_readers:
        raise ValueError("could not draw enough distinct readers")
    w["repo_reader"] = (rr[keep], ru[keep])
    return w


def _probe_index(w, size: dict) -> dict:
    ix = w.get("_probe_index")
    if ix is None:
        ix = w["_probe_index"] = {
            "team_members": CSR(*w["team_user"], size["teams"]),
            "org_teams": CSR(*w["org_team"], size["orgs"]),
            "org_users": CSR(*w["org_user"], size["orgs"]),
        }
    return ix


def _one_of(csr: CSR, rng, nodes):
    """One entry of each node's list, uniformly (lists are not empty)."""
    lo, hi = csr.start[nodes], csr.start[nodes + 1]
    return csr.dst[lo + (rng.random(nodes.shape[0]) * (hi - lo)).astype(np.int64)]


def make_probes(w, size: dict, rng, n: int):
    """``n`` (repo, user) probes: a quarter uniform (mostly denied), a
    quarter direct readers, a quarter maintainers through the team, a
    quarter through the org arrow (its admin, a direct member, or a
    member of one of its teams, a third each)."""
    ix = _probe_index(w, size)
    R, U = size["repos"], size["users"]
    q = n // 4
    repos = [rng.integers(0, R, n - 3 * q)]
    users = [rng.integers(0, U, n - 3 * q)]
    pick = rng.integers(0, w["repo_reader"][0].shape[0], q)
    repos.append(w["repo_reader"][0][pick])
    users.append(w["repo_reader"][1][pick])
    r = rng.integers(0, R, q)
    repos.append(r)
    users.append(_one_of(ix["team_members"], rng, w["repo_team"][1][r]))
    r = rng.integers(0, R, q)
    org = w["repo_org"][1][r]
    how = rng.integers(0, 3, q)
    u = np.where(how == 0, w["org_admin"][1][org],
                 _one_of(ix["org_users"], rng, org))
    via = how == 2
    u[via] = _one_of(ix["team_members"], rng,
                     _one_of(ix["org_teams"], rng, org[via]))
    repos.append(r)
    users.append(u)
    order = rng.permutation(n)
    return np.concatenate(repos)[order], np.concatenate(users)[order]


def reference(w, size: dict):
    """``check(repos, users)`` → ``repo#read`` for each pair, from the edge
    lists alone: reader, or maintainer through a team, or admin or member
    (direct or through a team) of the repo's org."""
    team_user = pair_keys(*w["team_user"])
    reader = pair_keys(*w["repo_reader"])
    repo_team = CSR(*w["repo_team"], size["repos"])
    repo_org = CSR(*w["repo_org"], size["repos"])
    org_direct = [pair_keys(*w[k]) for k in ("org_admin", "org_user")]
    org_team = CSR(*w["org_team"], size["orgs"])

    def check(repos, users) -> np.ndarray:
        n = repos.shape[0]

        def through_team(teams_of, rows, nodes):
            r, t = teams_of.expand(rows, nodes)
            return any_by_row(r, has_pair(team_user, t, users[r]), n)

        rows = np.arange(n)
        out = has_pair(reader, repos, users)
        out |= through_team(repo_team, rows, repos)
        rows, org = repo_org.expand(rows, repos)
        for direct in org_direct:
            out |= any_by_row(rows, has_pair(direct, org, users[rows]), n)
        return out | through_team(org_team, rows, org)

    return check
