"""The generators give exactly the stated edges with no duplicate pair, and
the plain reference agrees with the program's own host oracle — for the two
worlds of the benchmark and for the tests' own caveated world
(``fixture/``: an edge list of three columns, probes of three columns, checks
that carry request context)."""

import os

import numpy as np
import pytest

import run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture", "manifest.json")
#: cell -> (manifest, the configuration's full-size edges, the share of the
#: drawn probes that is granted)
CELLS = {"docs10m.bulk": (run.MANIFEST, 10_000_000, (0.6, 0.9)),
         "rbac10m.bulk": (run.MANIFEST, 10_000_000, (0.6, 0.9)),
         "caveats40k.served": (FIXTURE, 40_000, (0.45, 0.6))}


@pytest.fixture(scope="module", params=sorted(CELLS))
def world(request):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(run, "MANIFEST", CELLS[request.param][0])
        cell = run.load_cell(request.param, rehearse=True)
    return cell, cell["world"].build_world(cell["sizes"], 7)


def test_edge_count_is_exact_and_pairs_are_distinct(world):
    cell, w = world
    total = 0
    for key, *_ in cell["world"].SHAPES:
        a, b, *_ = w[key]  # a world may give an edge list more columns
        total += a.shape[0]
        assert np.unique(a.astype(np.int64) << 32 | b).shape[0] == a.shape[0], key
    assert total == cell["sizes"]["edges"]
    assert cell["config"]["sizes"]["edges"] == CELLS[cell["name"]][1]


def test_same_seed_same_world_other_seed_other_world(world):
    cell, w = world
    again = cell["world"].build_world(cell["sizes"], 7)
    other = cell["world"].build_world(cell["sizes"], 2**31 + 5)
    newest = cell["world"].NEWEST
    assert all(np.array_equal(w[k][1], again[k][1]) for k, *_ in cell["world"].SHAPES)
    assert not np.array_equal(w[newest][1], other[newest][1])


def test_reference_agrees_with_the_programs_oracle(world):
    """A few hundred probes of each kind (make_probes draws a quarter of
    each), against engine/oracle.py over the imported store.  The probes go
    the way the entries send them: a tuple of columns, the reference over all
    of them, the world's own ``probe_rels`` where it has one."""
    from gochugaru_tpu import consistency
    from gochugaru_tpu.engine.oracle import SnapshotOracle, T
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(1)
    cell, w = world
    program = run.Program(cell, w, lambda *a, **k: None)
    store = program.client.store
    snap = store.snapshot_for(consistency.full())
    oracle = SnapshotOracle(snap, {name: store.caveat_program(name)
                                   for name in snap.compiled.schema.caveats})
    columns = cell["world"].make_probes(
        w, cell["sizes"], np.random.default_rng(3), 1600)
    want = cell["world"].reference(w, cell["sizes"])(*columns)
    rels = run._checks.probe_rels(cell["world"], columns)
    got = np.array([oracle.check_relationship(r) == T for r in rels])
    assert np.array_equal(got, want)
    # every kind of probe is there: most of the drawn grants hold, most
    # uniform probes are denied (and in the caveated world a holder under
    # another tenant)
    lo, hi = CELLS[cell["name"]][2]
    assert lo < want.mean() < hi
