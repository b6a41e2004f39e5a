"""``worlds/rbac_zipf.py`` draws what its configuration states, and the
cell ``rbac10m_zipf.cached`` is ``correct`` with the cache answering, and
not ``correct`` under the control and under each planted fault."""

import json

import numpy as np
import pytest

import control
import run

CELL = "rbac10m_zipf.cached"


@pytest.fixture(scope="module")
def world():
    cell = run.load_cell(CELL, rehearse=True)
    return cell, cell["world"].build_world(cell["sizes"], 7)


def test_same_seed_same_probes_and_records_is_honoured(world):
    cell, w = world
    mod, sizes = cell["world"], cell["sizes"]
    # probes as the entries read them: a tuple of columns, however many
    draw = lambda s: mod.make_probes(w, sizes, np.random.default_rng([s, 1]), 5000)
    assert all(np.array_equal(a, b) for a, b in zip(draw(7), draw(7)))
    assert not any(np.array_equal(a, b) for a, b in zip(draw(7), draw(8)))
    assert w["records"][0].shape == (sizes["records"],) == w["zipfian_cdf"].shape
    assert cell["config"]["sizes"]["records"] == 10_000_000
    at = mod.record_indices(w, np.random.default_rng(3), 200_000)
    assert 0 == at.min() and at.max() < sizes["records"]
    # a smaller table is honoured too: nothing is drawn past its end
    small = {**w, "zipfian_cdf": mod.zipfian_cdf(1000)}
    assert mod.record_indices(small, np.random.default_rng(3), 50_000).max() < 1000
    # the record table is the world's own probe mix: most grants hold
    assert 0.6 < mod.reference(w, sizes)(*w["records"]).mean() < 0.9


def test_the_ten_commonest_records_follow_the_zipfian_pmf(world):
    cell, w = world
    records = cell["sizes"]["records"]
    at = cell["world"].record_indices(w, np.random.default_rng(11), 1_000_000)
    got = np.bincount(at, minlength=records)[:10] / at.shape[0]
    ranks = np.arange(1, records + 1, dtype=np.float64)
    pmf = ranks ** -0.99 / (ranks ** -0.99).sum()
    assert np.all(np.abs(got / pmf[:10] - 1.0) < 0.05)
    assert np.all(np.diff(got) < 0)  # rank 1 is the commonest


def result_of(capsys, make_program=run.Program):
    args = run.parse_args(["--workload", CELL, "--seed", "2700000009",
                           "--seconds", "1", "--trace", "0", "--rehearse-cpu"])
    assert run.run_cell(args, make_program=make_program) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cached_cell_is_correct_and_the_cache_answers(capsys):
    from gochugaru_tpu.utils import metrics

    hits = metrics.default.counter("cache.hits")
    line = result_of(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checked"]["answers_compared"]["value"] > 1000
    assert metrics.default.counter("cache.hits") > hits
    assert set(line["metrics"]) >= {"checks_per_s", "request_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(control.CONTROLS))
def test_the_cached_cell_is_not_correct_under_a_fault(capsys, fault):
    line = result_of(capsys, control.CONTROLS[fault])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0
