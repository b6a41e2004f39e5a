"""What a world may own since PR 35, proven on one small caveated world
(``fixture/``: the shape of BASELINE.md row 4 at 40,000 edges, a fixture of
these tests and no configuration of the benchmark) driven through
``run.run_cell`` with ``--rehearse-cpu``: its own loader (holder edges
``with same_tenant`` and a stored context), probes of three columns (item,
user, tenant), checks that carry request context, and a stale control that
cuts every column of ``w[NEWEST]``."""

import json
import os

import pytest

import control
import run

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture", "manifest.json")
CELLS = ("caveats40k.served", "caveats40k.bulk")
HOST_RESOLVED = ("checks.oracle", "checks.fallback_conditional",
                 "checks.fallback_overflow")


@pytest.fixture(autouse=True)
def fixture_manifest(monkeypatch):
    monkeypatch.setattr(run, "MANIFEST", FIXTURE)


def run_of(capsys, workload: str, make_program=run.Program, trace: int = 0):
    """(the run's events by name, its result line)."""
    args = run.parse_args(["--workload", workload, "--seed", "2700000009",
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearse-cpu"])
    assert run.run_cell(args, make_program=make_program) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    return {l["event"]: l for l in lines[:-1]}, lines[-1]


def host_resolved() -> list:
    from gochugaru_tpu.utils import metrics

    return [metrics.default.counter(k) for k in HOST_RESOLVED]


def watched(seen: list):
    """The program, with the host-resolved counters read around set-up's
    first answer: ``seen`` gets (the checks sent, before, after)."""

    def make(cell, w, say):
        program = run.Program(cell, w, say)
        first_answer = program.first_answer

        def watching(rels):
            before = host_resolved()
            took = first_answer(rels)
            seen.append((rels, before, host_resolved()))
            return took

        program.first_answer = watching
        return program

    return make


@pytest.mark.parametrize("workload", CELLS)
def test_unbroken_run_is_correct_and_no_check_was_resolved_on_the_host(capsys, workload):
    at_start, seen = host_resolved(), []
    events, line = run_of(capsys, workload, watched(seen))
    assert line["correct"] is True and line["failed"] == 0
    assert line["checked"]["answers_compared"]["value"] > 1000
    assert events["loaded"]["loader"] == "world.load_edges"
    assert events["loaded"]["edges"] == 40_000
    # the first answer is four of the cell's own probes, request context and
    # all, and the device answered it: set-up paid for nothing the cell's
    # traffic would not cause
    (rels, before, after), = seen
    assert len(rels) == 4 and all(r.caveat_context["tier"] == 2 and
                                  r.caveat_context["tenant"].startswith("t")
                                  for r in rels)
    assert before == after
    # nor did the warm-up or the window send one to the host
    assert host_resolved() == at_start


def test_the_traced_run_reads_no_host_resolved_check(capsys):
    _, line = run_of(capsys, "caveats40k.served", trace=1)
    assert line["correct"] is True
    assert line["metrics"]["client.host_resolved_share"]["value"] == 0.0
    assert line["metrics"]["engine.window_compiles"]["value"] == 0.0


@pytest.mark.parametrize("fault,workload", [
    ("stale", "caveats40k.served"), ("stale", "caveats40k.bulk"),
    ("flipped", "caveats40k.served"), ("short", "caveats40k.served"),
    ("flipped", "caveats40k.bulk"),
])
def test_the_fixture_cell_is_not_correct_under_a_fault(capsys, fault, workload):
    events, line = run_of(capsys, workload, control.CONTROLS[fault])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0
    if fault == "stale":  # the newest 1 % of the caveated edges, all columns
        assert events["control"]["of"] == "item_holder"
        assert events["control"]["hidden_edges"] == 339


def test_a_world_without_a_loader_goes_in_by_its_shapes(capsys, monkeypatch):
    monkeypatch.setattr(run, "MANIFEST", os.path.join(run.ROOT, "BENCHMARK.json"))
    events, line = run_of(capsys, "rbac10m.bulk")
    assert events["loaded"]["loader"] == "run.import_shapes"
    assert line["correct"] is True


def test_the_stale_world_keeps_its_columns_aligned():
    """Every column of the newest edge list is cut to the same length: a
    holder that stays keeps its own tenant."""
    import numpy as np

    cell = run.load_cell("caveats40k.served", rehearse=True)
    w = cell["world"].build_world(cell["sizes"], 7)
    stale = control.StaleReference(cell, w, lambda *a, **k: None)
    items, users, tenants = (c[:100] for c in w["item_holder"])
    req = run._checks.Request(0, (items, users, tenants), None)
    assert stale.entry(req) == [True] * 100
    other = run._checks.Request(0, (items, users, (tenants + 1) % 16), None)
    assert sum(stale.entry(other)) < 5  # but for an admin who also holds
    hidden = run._checks.Request(0, tuple(c[-100:] for c in w["item_holder"]), None)
    assert sum(stale.entry(hidden)) < 5 and np.all(
        cell["world"].reference(w, cell["sizes"])(*hidden.columns))
