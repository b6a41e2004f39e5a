"""``correct`` comes out true on the unbroken path and false under the
control and under each fault a cell of this benchmark can have.  These
skip the harness's look for a chip (``--rehearse-cpu``) and drive the rest
of a run."""

import json

import pytest

import control
import run


def result_of(capsys, workload: str, make_program=run.Program, seed: int = 9):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", "1", "--trace", "0", "--rehearse-cpu"])
    assert run.run_cell(args, make_program=make_program) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    # the numbers compared stand beside their limits, last in the line and
    # last on standard error
    assert list(line)[-1] == "checked"
    assert json.loads(out.err.strip().splitlines()[-1])["checked"] == line["checked"]
    return line


@pytest.mark.parametrize("workload", ["rbac10m.bulk", "docs10m.point"])
def test_unbroken_run_is_correct(capsys, workload):
    line = result_of(capsys, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checked"]["answers_compared"]["value"] > 1000
    assert set(line["metrics"]) >= {"checks_per_s", "request_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", ["rbac10m.bulk", "docs10m.bulk"])
def test_stale_reference_in_the_programs_place_is_not_correct(capsys, workload):
    line = result_of(capsys, workload, control.CONTROLS["stale"])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault,workload", [
    ("flipped", "rbac10m.bulk"), ("flipped", "docs10m.point"),
    ("short", "rbac10m.bulk"),
])
def test_a_broken_timed_path_is_not_correct(capsys, fault, workload):
    line = result_of(capsys, workload, control.CONTROLS[fault])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0


def test_a_request_that_raises_in_the_window_is_not_correct(capsys, monkeypatch):
    window = []  # run_cell freezes the heap just before its window
    monkeypatch.setattr(run.gc, "freeze", lambda: window.append(True))

    def raising(cell, w, say):
        program = run.Program(cell, w, say)
        entry = program.entry

        def in_the_window(req):
            if req.index == 1 and window:
                raise TimeoutError("planted")
            return entry(req)

        program.entry = in_the_window
        return program

    line = result_of(capsys, "rbac10m.bulk", raising)
    assert line["correct"] is False and line["failed"] > 0
    assert line["checked"]["unanswered_requests"]["value"] == line["failed"]
