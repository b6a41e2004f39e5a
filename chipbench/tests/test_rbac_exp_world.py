"""The expiring RBAC deployment's world (``worlds/rbac_exp.py``, configuration
``rbac10m_exp``, cell ``rbac10m_exp.bulk``) at its rehearsal sizes: ``rbac``'s
edges and no more, the expiring edge lists with offsets in the stated range
and share, the program's oracle agreeing with the reference, a rehearsal that
comes out correct and reads both expiry metrics, a stale control and a load
with no expiry that do not, and the request pools a seed draws: ``rbac10m``'s
own (``data/parent_pools.json``), pinned by digest besides
(``data/rbac_exp_pools.json``, written by the PR that added the cell)."""

import hashlib
import json
import os

import numpy as np
import pytest

import control
import no_expiry
import run

CELL = "rbac10m_exp.bulk"
SEED = "3000000019"
HOUR_US = 3600 * 1_000_000
HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "data", "rbac_exp_pools.json")) as f:
    POOLS = json.load(f)
with open(os.path.join(HERE, "data", "parent_pools.json")) as f:
    RBAC_POOLS = {k.split(":")[1]: v for k, v in json.load(f).items()
                  if k.startswith("rbac10m.bulk:")}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL, rehearse=True)


@pytest.fixture(scope="module")
def world(cell):
    return cell["world"].build_world(cell["sizes"], 7)


def test_the_world_holds_rbacs_edges_and_their_expiries(cell, world):
    mod, sizes = cell["world"], cell["sizes"]
    plain, total = mod.rbac.build_world(sizes, 7), 0
    for key, *_ in mod.SHAPES:
        cols = world[key]
        assert len(cols) == (3 if key in mod.EXPIRING else 2), key
        assert all(np.array_equal(a, b) for a, b in zip(cols[:2], plain[key]))
        total += cols[0].shape[0]
    assert total == sizes["edges"] == 100_000
    offsets = np.concatenate([world[k][2] for k in mod.EXPIRING])
    assert offsets.shape[0] == 100_000 - sizes["orgs"] - sizes["repos"]
    assert -24 * HOUR_US <= offsets.min() and offsets.max() <= 90 * 24 * HOUR_US
    assert not ((-HOUR_US < offsets) & (offsets < HOUR_US)).any()
    # 23 h of the 2,182 h the offsets range over lie before the load
    assert 0.009 < (offsets < 0).mean() < 0.012
    full = cell["config"]["sizes"]
    assert full["edges"] - full["orgs"] - full["repos"] == 7_998_000


def test_the_reference_agrees_with_the_programs_oracle(cell, world):
    from gochugaru_tpu import consistency
    from gochugaru_tpu.engine.oracle import SnapshotOracle, T
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(1)
    events = []
    program = run.Program(cell, world, lambda e, **k: events.append((e, k)))
    assert events[0][1]["loader"] == "world.load_edges"
    snap = program.client.store.snapshot_for(consistency.full())
    assert np.count_nonzero(snap.e_exp_us) == 100_000 - 20 - 20_000
    oracle = SnapshotOracle(snap, {})
    mod = cell["world"]
    columns = mod.make_probes(world, cell["sizes"], np.random.default_rng(3), 2000)
    want = mod.reference(world, cell["sizes"])(*columns)
    rels = run._checks.probe_rels(mod, columns)
    got = np.array([oracle.check_relationship(r) == T for r in rels])
    assert np.array_equal(got, want)
    # an expired edge only ever takes a grant away, and some probes hit one
    stored = mod.reference(no_expiry.every_edge(world), cell["sizes"])(*columns)
    assert not (want & ~stored).any() and (stored & ~want).any()
    assert 0.6 < want.mean() < 0.9


def run_of(capsys, make_program=run.Program, trace: int = 0):
    args = run.parse_args(["--workload", CELL, "--seed", SEED, "--seconds", "1",
                           "--trace", str(trace), "--rehearse-cpu"])
    assert run.run_cell(args, make_program=make_program) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    return {l["event"]: l for l in lines[:-1]}, lines[-1]


def test_the_rehearsal_is_correct_and_reads_the_expiry_metrics(capsys):
    from gochugaru_tpu.utils import metrics

    stored = metrics.default.counter("store.expiring_rows")
    events, line = run_of(capsys, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert events["loaded"]["loader"] == "world.load_edges"
    assert events["loaded"]["edges"] == 100_000
    assert metrics.default.counter("store.expiring_rows") - stored == 79_980
    got = line["metrics"]
    assert got["client.host_resolved_share"]["value"] == 0.0
    assert got["engine.window_compiles"]["value"] == 0.0
    assert got["engine.expiry_gated_share"]["value"] == 100.0
    assert 0 < got["prepare.expiry_s"]["value"] < got["prepare.total_s"]["value"]


def test_the_stale_control_is_not_correct(capsys):
    events, line = run_of(capsys, control.CONTROLS["stale"])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0
    assert events["control"]["of"] == "repo_reader"


def test_the_load_with_no_expiry_is_not_correct_by_the_predicted_count(capsys):
    events, line = run_of(capsys, no_expiry.unexpiring)
    assert line["correct"] is False and line["failed"] == 0
    wrong = line["checked"]["wrong_answers"]["value"]
    assert wrong == events["predicted"]["wrong_answers"] > 0


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()


def pools_of(seed: int) -> dict:
    """What a seed draws at the rehearsal sizes: the pool (``[seed, 1]``),
    set-up's four first probes and the warm requests (``[seed, 2]``), in
    ``data/parent_pools.json``'s form."""
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
        "res": digest([r.columns[0] for r in pool]),
        "subj": digest([r.columns[1] for r in pool]),
        "set_up": digest(list(first) + [c for r in warm for c in r.columns]),
    }


@pytest.mark.parametrize("seed", sorted(POOLS))
def test_a_seed_gives_the_pools_it_gave(seed):
    assert pools_of(int(seed)) == POOLS[seed]


@pytest.mark.parametrize("seed", sorted(RBAC_POOLS))
def test_a_seed_gives_rbac10ms_pools(seed):
    assert pools_of(int(seed)) == RBAC_POOLS[seed]
