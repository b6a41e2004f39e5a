"""``rbac`` with time-bound access: every grant to a user or a team expires,
as GitLab's access expiration date on project and group members and on
group shares, and as SpiceDB's ``use expiration``.  A configuration may
differ from another in one trait alone: the world's edge lists, the probe
mix and the plain reference's walk are ``worlds/rbac.py``'s, unchanged;
what is new is the schema's ``with expiration`` traits, an expiry on each
edge of the ``EXPIRING`` shapes, and the reference dropping the edges
already expired.

An expiry is an offset from the instant the edges are loaded, drawn from
``[seed, 3]`` uniform over [-24 h, +90 d] with (-1 h, +1 h) left out: about
1.05 % of the expiring edges have expired (up to a day before the load) and
are stored all the same, and none lapses within an hour of the load, so
every instant of a run has the same answers.  An edge list of ``EXPIRING``
is three columns, (resource, subject, offset micros).

Index space throughout: object i of a type is ``<prefix><i>``.
"""

from __future__ import annotations

import importlib.util
import os
import time

import numpy as np

HOUR_US = 3600 * 1_000_000
#: the offsets' range and the gap around the load left out of it
EARLIEST_US, LATEST_US, GAP_US = -24 * HOUR_US, 90 * 24 * HOUR_US, HOUR_US


def _load_rbac():
    # worlds/ is not on the path (run.py loads a world by file name)
    spec = importlib.util.spec_from_file_location(
        "chipbench_worlds_rbac",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "rbac.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rbac = _load_rbac()
TYPES, SHAPES, PROBE, NEWEST = rbac.TYPES, rbac.SHAPES, rbac.PROBE, rbac.NEWEST

SCHEMA = """
use expiration

definition user {}
definition team { relation member: user with expiration }
definition org {
    relation admin: user
    relation member: user with expiration | team#member with expiration
}
definition repo {
    relation org: org
    relation maintainer: user | team#member with expiration
    relation reader: user with expiration
    permission admin = org->admin + maintainer
    permission read = reader + admin + org->member
}
"""

#: the same schema without the traits: what a load with no expiry writes
PLAIN_SCHEMA = rbac.SCHEMA

#: the shapes whose every edge carries an expiry (``org_admin`` and
#: ``repo_org`` never expire)
EXPIRING = ("team_user", "org_user", "org_team", "repo_team", "repo_reader")


def draw_offsets(rng, n: int) -> np.ndarray:
    """``n`` offsets in micros, uniform over [EARLIEST, LATEST] less the
    open gap (-GAP, +GAP)."""
    past = -GAP_US - EARLIEST_US
    u = rng.integers(0, past + LATEST_US - GAP_US, n)
    return np.where(u < past, EARLIEST_US + u, GAP_US + (u - past))


def build_world(size: dict, seed: int) -> dict:
    """``rbac``'s world, each ``EXPIRING`` edge list with its offsets."""
    w = rbac.build_world(size, seed)
    rng = np.random.default_rng([seed, 3])
    for key in EXPIRING:
        r, s = w[key]
        w[key] = (r, s, draw_offsets(rng, r.shape[0]))
    return w


def pairs(w) -> dict:
    """The world as ``rbac`` holds it: every edge list's first two columns,
    the expired edges too."""
    return {key: w[key][:2] for key, *_ in SHAPES}


def load_edges(client, ctx, ids: dict, w: dict, size: dict,
               expiring: bool = True) -> int:
    """Every edge list as id columns, one call each; those of ``EXPIRING``
    with ``expirations`` = the load instant + their offsets.  ``expiring``
    False loads the same edges with no expiry at all, under
    ``PLAIN_SCHEMA`` (the reading that has to come out as not correct).
    Returns the edges imported."""
    loaded_us = time.time_ns() // 1000
    edges = 0
    for key, rtype, relation, stype, srel in SHAPES:
        r, s, *offsets = w[key]
        exp = {"expirations": loaded_us + offsets[0]} if offsets and expiring else {}
        client.import_relationship_id_columns(
            ctx(), resource_ids=ids[rtype][r], resource_relation=relation,
            subject_ids=ids[stype][s], subject_relation=srel, **exp)
        edges += int(r.shape[0])
    return edges


def make_probes(w, size: dict, rng, n: int):
    """``rbac``'s quarter mix over every stored edge, expired or not."""
    held = w.get("_pairs")
    if held is None:
        held = w["_pairs"] = pairs(w)
    return rbac.make_probes(held, size, rng, n)


def reference(w, size: dict):
    """``check(repos, users)`` → ``repo#read`` at any instant of the run:
    ``rbac``'s reference over the edges whose offset is not negative."""
    live = {}
    for key, *_ in SHAPES:
        cols = w[key]
        keep = cols[2] >= 0 if len(cols) > 2 else slice(None)
        live[key] = (cols[0][keep], cols[1][keep])
    return rbac.reference(live, size)
