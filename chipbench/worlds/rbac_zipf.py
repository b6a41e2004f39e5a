"""``rbac`` under skewed keys: YCSB core workload C (read only,
``requestdistribution=zipfian``, constant 0.99) over the same world.  A
configuration may differ from another in its key distribution alone: the
schema, the edge lists and the plain reference are ``worlds/rbac.py``'s,
unchanged; what is new is the table of *records* and how a probe is drawn
from it.

A record is one (repo, user) check pair.  ``size['records']`` of them are
drawn once per world from ``rbac``'s own probe mix (a quarter uniform, a
quarter readers, a quarter through the team, a quarter through the org),
in the random order that mix ends in, so record ``i`` has rank ``i + 1``
and the rank -> record map is random by construction (what YCSB's
scrambled zipfian is for).  A probe is a record index by inverse CDF of
p(i) ~ i^-0.99, i = 1...records.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

ZIPFIAN_CONSTANT = 0.99  # YCSB's ZipfianGenerator.ZIPFIAN_CONSTANT


def _load_rbac():
    # worlds/ is not on the path (run.py loads a world by file name)
    spec = importlib.util.spec_from_file_location(
        "chipbench_worlds_rbac",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "rbac.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rbac = _load_rbac()
SCHEMA, TYPES, SHAPES = rbac.SCHEMA, rbac.TYPES, rbac.SHAPES
PROBE, NEWEST = rbac.PROBE, rbac.NEWEST
reference = rbac.reference


def zipfian_cdf(records: int) -> np.ndarray:
    """Unnormalised CDF of p(i) ~ i^-0.99 over ranks 1...records."""
    return np.cumsum(np.arange(1, records + 1, dtype=np.float64)
                     ** -ZIPFIAN_CONSTANT)


def build_world(size: dict, seed: int) -> dict:
    """``rbac``'s world, plus the record table and the zipfian CDF."""
    w = rbac.build_world(size, seed)
    w["records"] = rbac.make_probes(
        w, size, np.random.default_rng([seed, 3]), size["records"])
    w["zipfian_cdf"] = zipfian_cdf(size["records"])
    return w


def record_indices(w, rng, n: int) -> np.ndarray:
    """``n`` record indices (rank - 1), zipfian, by inverse CDF."""
    cdf = w["zipfian_cdf"]
    at = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return np.minimum(at, cdf.shape[0] - 1)


def make_probes(w, size: dict, rng, n: int):
    """``n`` (repo, user) probes: the records at ``n`` zipfian ranks."""
    at = record_indices(w, rng, n)
    repos, users = w["records"]
    return repos[at], users[at]
