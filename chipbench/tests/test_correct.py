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


@pytest.mark.parametrize("workload", ["rbac10m.bulk", "docs10m.point",
                                      "docs10m.lookup"])
def test_unbroken_run_is_correct(capsys, workload):
    line = result_of(capsys, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["checked"]["answers_compared"]["value"] > 1000
    cell = run.load_cell(workload, True)
    rates = set(cell["entry"].RATES)
    assert rates < {"checks_per_s", "lookups_per_s"}  # one of them, its own
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]} >= (
        rates | {"device_bytes_per_edge", "setup_s"})
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", ["rbac10m.bulk", "docs10m.bulk",
                                      "docs10m.lookup"])
def test_stale_reference_in_the_programs_place_is_not_correct(capsys, workload):
    line = result_of(capsys, workload, control.CONTROLS["stale"])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault,workload", [
    ("flipped", "rbac10m.bulk"), ("flipped", "docs10m.point"),
    ("short", "rbac10m.bulk"),
    ("flipped", "docs10m.lookup"), ("short", "docs10m.lookup"),
])
def test_a_broken_timed_path_is_not_correct(capsys, fault, workload):
    line = result_of(capsys, workload, control.CONTROLS[fault])
    assert line["correct"] is False
    assert line["checked"]["wrong_answers"]["value"] > 0


def test_a_repeated_id_alone_is_not_correct(capsys):
    """The ids of a lookup are judged as a set in which none comes twice."""

    def repeating(cell, w, say):
        program = run.Program(cell, w, say)
        entry = program.entry
        program.entry = lambda req: (
            entry(req) + entry(req)[:1] if req.index == 0 else entry(req))
        return program

    line = result_of(capsys, "docs10m.lookup", repeating)
    assert line["correct"] is False and line["failed"] == 0
    sent = line["attempted"] // 32 + 1  # request 0 of the pool of 32, each cycle
    assert 1 <= line["checked"]["wrong_answers"]["value"] <= sent


def test_an_unknown_entry_point_names_the_files_under_entries():
    with pytest.raises(SystemExit) as refused:
        run.load_entry("client.expand")
    for entry in ("client.check", "serving.check", "client.lookup"):
        assert entry in str(refused.value)
    assert "_checks" not in str(refused.value)


@pytest.mark.parametrize("workload", ["rbac10m.bulk", "docs10m.lookup"])
def test_a_request_that_raises_in_the_window_is_not_correct(capsys, monkeypatch,
                                                           workload):
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

    line = result_of(capsys, workload, raising)
    assert line["correct"] is False and line["failed"] > 0
    assert line["checked"]["unanswered_requests"]["value"] == line["failed"]


def test_a_warm_pass_sends_every_request_of_the_pool_once_and_the_clock_none_late():
    import types

    pool = [types.SimpleNamespace(index=i) for i in range(10)]
    _, log, hung = run.drive(lambda req: req.index * 2, pool, 4, 0.0, False, passes=2)
    assert hung == 0 and sorted(e[0] for e in log) == sorted(list(range(10)) * 2)
    assert all(out == index * 2 for index, _sent, _answered, out in log)
    _, log, hung = run.drive(lambda req: req.index, pool, 4, 0.0, False)
    assert hung == 0 and log == []  # by the clock: none starts after 0 s
