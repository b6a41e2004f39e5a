"""The caveat deployment's world (``worlds/caveats.py``, configuration
``caveats12m``, cell ``caveats12m.bulk``) at its rehearsal sizes: the stated
edges and no more, the same world and reference as the tests' fixture world
on the same seed, the program's oracle agreeing with the reference, a
rehearsal that comes out correct and a stale control that does not, and the
request pools a seed draws, pinned by digest (``data/caveats_pools.json``,
written by the PR that added the cell)."""

import hashlib
import json
import os

import numpy as np
import pytest

import control
import run

CELL = "caveats12m.bulk"
HERE = os.path.dirname(__file__)
FIXTURE_BENCH = os.path.join(HERE, "fixture", "bench")
with open(os.path.join(HERE, "data", "caveats_pools.json")) as f:
    POOLS = json.load(f)


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL, rehearse=True)


@pytest.fixture(scope="module")
def world(cell):
    return cell["world"].build_world(cell["sizes"], 7)


def test_the_world_holds_exactly_the_stated_edges(cell, world):
    sizes, total = cell["sizes"], 0
    for key, *_ in cell["world"].SHAPES:
        a, b, *rest = world[key]
        total += a.shape[0]
        assert np.unique(a.astype(np.int64) << 32 | b).shape[0] == a.shape[0], key
        for c in rest:  # the holders' tenant: one a holder, in range
            assert c.shape == a.shape and 0 <= c.min() and c.max() < sizes["tenants"]
    assert total == sizes["edges"] == 125_000
    assert world["item_holder"][0].shape[0] == 125_000 - 12_500 - 20
    assert cell["config"]["sizes"]["edges"] == 12_500_000
    full = cell["config"]["sizes"]
    assert full["edges"] - full["items"] - full["orgs"] == 11_248_000


def test_world_and_reference_are_the_fixture_worlds(cell, world):
    fixture = run.load_module("worlds", "caveats", (FIXTURE_BENCH,))
    mine = cell["world"]
    assert fixture.__file__ != mine.__file__
    assert mine.SCHEMA == fixture.SCHEMA and mine.SHAPES == fixture.SHAPES
    theirs = fixture.build_world(cell["sizes"], 7)
    for key, *_ in mine.SHAPES:
        assert all(np.array_equal(a, b) for a, b in zip(world[key], theirs[key]))
    columns = mine.make_probes(world, cell["sizes"], np.random.default_rng(5), 4000)
    again = fixture.make_probes(theirs, cell["sizes"], np.random.default_rng(5), 4000)
    assert all(np.array_equal(a, b) for a, b in zip(columns, again))
    assert np.array_equal(mine.reference(world, cell["sizes"])(*columns),
                          fixture.reference(theirs, cell["sizes"])(*columns))


def test_the_reference_agrees_with_the_programs_oracle(cell, world):
    from gochugaru_tpu import consistency
    from gochugaru_tpu.engine.oracle import SnapshotOracle, T
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(1)
    events = []
    program = run.Program(cell, world, lambda e, **k: events.append((e, k)))
    assert events[0][1]["loader"] == "world.load_edges"
    store = program.client.store
    snap = store.snapshot_for(consistency.full())
    oracle = SnapshotOracle(snap, {name: store.caveat_program(name)
                                   for name in snap.compiled.schema.caveats})
    columns = cell["world"].make_probes(
        world, cell["sizes"], np.random.default_rng(3), 1600)
    want = cell["world"].reference(world, cell["sizes"])(*columns)
    rels = run._checks.probe_rels(cell["world"], columns)
    assert all(r.caveat_context["tier"] == 2 for r in rels)
    got = np.array([oracle.check_relationship(r) == T for r in rels])
    assert np.array_equal(got, want)
    assert 0.45 < want.mean() < 0.6


def run_of(capsys, make_program=run.Program, trace: int = 0):
    args = run.parse_args(["--workload", CELL, "--seed", "3000000019",
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearse-cpu"])
    assert run.run_cell(args, make_program=make_program) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    return {l["event"]: l for l in lines[:-1]}, lines[-1]


def test_the_rehearsal_is_correct_and_reads_the_context_metrics(capsys):
    events, line = run_of(capsys, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert events["loaded"]["loader"] == "world.load_edges"
    assert events["loaded"]["edges"] == 125_000
    got = line["metrics"]
    assert got["client.host_resolved_share"]["value"] == 0.0
    assert 0 < got["engine.context_share"]["value"] < 100
    assert 1 < got["engine.contexts_per_batch"]["value"] <= 4096


def test_the_stale_control_is_not_correct(capsys):
    events, line = run_of(capsys, control.CONTROLS["stale"])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0
    assert events["control"]["of"] == "item_holder"
    assert events["control"]["hidden_edges"] == 1124


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()


def pools_of(seed: int) -> dict:
    """What a seed draws at the rehearsal sizes: the pool (``[seed, 1]``),
    set-up's four first probes and the warm requests (``[seed, 2]``), every
    column of each, the tenant too."""
    cell = run.load_cell(CELL, rehearse=True)
    mod, entry = cell["world"], cell["entry"]
    w = mod.build_world(cell["sizes"], seed)
    pool = entry.requests(cell, w, np.random.default_rng([seed, 1]))
    rng = np.random.default_rng([seed, 2])
    first = mod.make_probes(w, cell["sizes"], rng, 4)
    warm = entry.warm_requests(cell, w, rng)
    return {
        "requests": len(pool), "first_rel": str(pool[0].rels[0]),
        "sizes": digest([np.array([len(r.rels) for r in pool])]),
        "columns": [digest([r.columns[i] for r in pool]) for i in range(3)],
        "set_up": digest(list(first) + [c for r in warm for c in r.columns]),
    }


@pytest.mark.parametrize("seed", sorted(POOLS))
def test_a_seed_gives_the_pools_it_gave(seed):
    assert pools_of(int(seed)) == POOLS[seed]
