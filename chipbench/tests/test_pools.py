"""A seed gives the three worlds of the benchmark the very request pools it
gave before probes became tuples of columns (PR 35): ``data/parent_pools.json``
holds SHA-256 digests of what commit 8409d8b produced at the rehearsal
sizes — the pool's request sizes, its ``res`` and ``subj`` (stream
``[seed, 1]``), and set-up's four first probes and warm requests (stream
``[seed, 2]``, drawn in that order).  ``docs``'s pools are compared on the
world as the seed alone draws it (``draw_world``): since the same PR its
``build_world`` also gives the top 17 folders' viewers one shape on every seed
(``fix_top``), which a probe through an ancestor folder sees."""

import hashlib
import json
import os

import numpy as np
import pytest

import run

with open(os.path.join(os.path.dirname(__file__), "data", "parent_pools.json")) as f:
    PARENT = json.load(f)


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case", sorted(PARENT))
def test_a_seed_gives_the_pools_the_parent_gave(case):
    name, seed = case.split(":")
    cell = run.load_cell(name, rehearse=True)
    mod, entry, seed = cell["world"], cell["entry"], int(seed)
    w = getattr(mod, "draw_world", mod.build_world)(cell["sizes"], seed)
    pool = entry.requests(cell, w, np.random.default_rng([seed, 1]))
    rng = np.random.default_rng([seed, 2])
    first = mod.make_probes(w, cell["sizes"], rng, 4)  # as run_cell draws them
    warm = entry.warm_requests(cell, w, rng)
    assert all(len(r.columns) == 2 for r in pool + warm)
    assert {
        "requests": len(pool), "first_rel": str(pool[0].rels[0]),
        "sizes": digest([np.array([len(r.rels) for r in pool])]),
        "res": digest([r.columns[0] for r in pool]),
        "subj": digest([r.columns[1] for r in pool]),
        "set_up": digest(list(first) + [c for r in warm for c in r.columns]),
    } == PARENT[case]
