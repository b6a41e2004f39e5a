"""The generators give exactly the stated edges with no duplicate pair, and
the plain reference agrees with the program's own host oracle."""

import numpy as np
import pytest

import run

CELLS = ("docs10m.bulk", "rbac10m.bulk")


@pytest.fixture(scope="module", params=CELLS)
def world(request):
    cell = run.load_cell(request.param, rehearse=True)
    return cell, cell["world"].build_world(cell["sizes"], 7)


def test_edge_count_is_exact_and_pairs_are_distinct(world):
    cell, w = world
    total = 0
    for key, *_ in cell["world"].SHAPES:
        a, b = w[key]
        total += a.shape[0]
        assert np.unique(a.astype(np.int64) << 32 | b).shape[0] == a.shape[0], key
    assert total == cell["sizes"]["edges"]
    assert cell["config"]["sizes"]["edges"] == 10_000_000


def test_same_seed_same_world_other_seed_other_world(world):
    cell, w = world
    again = cell["world"].build_world(cell["sizes"], 7)
    other = cell["world"].build_world(cell["sizes"], 2**31 + 5)
    newest = cell["world"].NEWEST
    assert all(np.array_equal(w[k][1], again[k][1]) for k, *_ in cell["world"].SHAPES)
    assert not np.array_equal(w[newest][1], other[newest][1])


def test_reference_agrees_with_the_programs_oracle(world):
    """A few hundred probes of each kind (make_probes draws a quarter of
    each), against engine/oracle.py over the imported store."""
    from gochugaru_tpu import consistency
    from gochugaru_tpu.engine.oracle import SnapshotOracle, T
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(1)
    cell, w = world
    program = run.Program(cell, w, lambda *a, **k: None)
    oracle = SnapshotOracle(program.client.store.snapshot_for(consistency.full()))
    res, subj = cell["world"].make_probes(
        w, cell["sizes"], np.random.default_rng(3), 1600)
    want = cell["world"].reference(w, cell["sizes"])(res, subj)
    rels = run._checks.to_rels(cell["world"].PROBE, res, subj)
    got = np.array([oracle.check_relationship(r) == T for r in rels])
    assert np.array_equal(got, want)
    # every kind of probe is there: most of the drawn grants hold, most
    # uniform probes are denied
    assert 0.6 < want.mean() < 0.9
