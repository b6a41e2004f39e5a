"""The lookup path's stages (utils/trace.py ``stage``), its ``lookup``
root span and the ``spmm.rounds`` counter, on the fused path and on the
looped one (``spmm_rounds=1`` sends every lookup of this world over the
fused program's round budget, so it falls back).

(a) tracing off, no profiler session: no Span, no annotation, every
stage the path runs gains a sample, ``client.lookup_s`` one a lookup;
(b) under ``jax.profiler.trace`` the ``gochugaru.lookup.*`` events are
there and are leaves; (c) sampled, one ``lookup`` root a lookup with the
path's tallies, its child spans the stage timers' samples exactly;
(d) the stages add up to no more than ``client.lookup_s``, lookup by
lookup."""

import dataclasses

import pytest

import test_lookup as tl
from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import new_tpu_evaluator, with_engine_config
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.utils import metrics, trace
from gochugaru_tpu.utils.context import background
from test_trace import (
    _CountingAnnotation,
    assert_stages_are_leaves,
    profiled_stage_events,
)

#: what every lookup of this world runs, whichever path
COMMON = ("lookup.resolve", "lookup.args", "lookup.fused.enqueue",
          "lookup.fused.fetch", "lookup.expand", "engine.lower",
          "engine.enqueue", "engine.fetch", "lookup.decode", "lookup.sort")
#: the looped path's hops besides (after the fused program overflowed)
STAGES = {"fused": COMMON,
          "looped": COMMON + ("lookup.hop.enqueue", "lookup.hop.fetch")}
#: every stage of the lookup path, whether this world runs it or not
ALL = STAGES["looped"] + ("lookup.oracle",)


@pytest.fixture(autouse=True)
def _trace_hygiene():
    trace.disable()
    yield
    trace.disable()


@pytest.fixture(scope="module", params=["fused", "looped"])
def world(request):
    """(path, client, ctx, cs, a user who reaches repos through a team,
    a repo) on the RBAC world; both lookups warmed (they compile)."""
    opts = []
    if request.param == "looped":
        opts.append(with_engine_config(
            dataclasses.replace(EngineConfig(), spmm_rounds=1)))
    c = new_tpu_evaluator(*opts)
    ctx = background()
    c.write_schema(ctx, tl.RBAC)
    rels, users, teams, _orgs, repos = tl.rbac_world()
    txn = rel.Txn()
    for r in rels:
        txn.create(r)
    cs = consistency.at_least(c.write(ctx, txn))
    member = next(r.subject_id for r in rels if r.resource_type == "team")
    user, repo = f"user:{member}", repos[0].split(":")[1]
    for got in lookups(c, ctx, cs, user, repo):
        assert got
    return request.param, c, ctx, cs, user, repo


def lookups(c, ctx, cs, user, repo):
    """A LookupResources and a LookupSubjects, each drained."""
    return (list(c.lookup_resources(ctx, cs, "repo#read", user)),
            list(c.lookup_subjects(ctx, cs, f"repo:{repo}", "read", "user")))


def timers(*stages):
    """(count, total seconds) of each stage's timer."""
    snap = metrics.default.snapshot()
    return {s: (snap.get(f"{s}_s.count", 0), snap.get(f"{s}_s.total_s", 0.0))
            for s in stages}


def gained(before, after):
    return {s: (after[s][0] - before[s][0], after[s][1] - before[s][1])
            for s in after if after[s][0] > before[s][0]}


def test_tracing_off_builds_nothing_and_times_every_stage(world, monkeypatch):
    """(a) and the answers: exactly the oracle's."""
    path, c, ctx, cs, user, repo = world
    _CountingAnnotation.built, _CountingAnnotation.live = [], False
    monkeypatch.setattr(trace, "_ANNOTATION", _CountingAnnotation)
    before, n0 = timers(*ALL, "client.lookup"), trace.spans_created()
    res, subj = lookups(c, ctx, cs, user, repo)
    after = timers(*ALL, "client.lookup")
    assert trace.spans_created() == n0
    assert _CountingAnnotation.built == []
    got = gained(before, after)
    assert got.pop("client.lookup")[0] == 2
    assert set(got) == set(STAGES[path]), set(got) ^ set(STAGES[path])
    oracle = c._oracle_for(c.store.snapshot_for(cs))
    assert res == sorted(oracle.lookup_resources(
        "repo", "read", "user", user.split(":")[1], ""))
    assert subj == sorted(oracle.lookup_subjects("repo", repo, "read", "user", ""))


def test_stages_land_in_the_profilers_trace_as_leaves(world, tmp_path):
    """(b): the timer-only ``client.lookup`` encloses them and is no
    event."""
    import jax

    path, c, ctx, cs, user, repo = world
    with jax.profiler.trace(str(tmp_path)):
        lookups(c, ctx, cs, user, repo)
    events = profiled_stage_events(tmp_path)
    names = {e[1] for e in events}
    want = {f"gochugaru.{s}" for s in STAGES[path]}
    assert want <= names, want - names
    assert "gochugaru.client.lookup" not in names
    assert_stages_are_leaves(events)


@pytest.mark.parametrize("kind", ["resources", "subjects"])
def test_sampled_lookup_is_one_root_whose_children_are_the_stages(world, kind):
    """(c): the root's attributes, and its child spans built from the
    same stamps as the timers (count and seconds, stage by stage)."""
    path, c, ctx, cs, user, repo = world
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=64)
    before = timers(*ALL)
    rounds0 = metrics.default.counter("spmm.rounds")
    if kind == "resources":
        ids = list(c.lookup_resources(ctx, cs, "repo#read", user))
    else:
        ids = list(c.lookup_subjects(ctx, cs, f"repo:{repo}", "read", "user"))
    stages = gained(before, timers(*ALL))
    [t] = [t for t in tr.traces() if t["name"] == "lookup"]
    root = t["spans"][0]
    assert root["parent_id"] == -1
    a = root["attrs"]
    assert a["kind"] == kind and a["ids"] == len(ids)
    assert a["path"] == path and a["fallback"] is (path == "looped")
    assert a["rounds"] == metrics.default.counter("spmm.rounds") - rounds0
    assert a["rounds"] >= (2 if path == "fused" else 1)
    assert a["dispatches"] >= (1 if path == "fused" else 2)
    assert (a.get("hops", 0) > 0) is (path == "looped")
    assert a["blocks"] >= 1 and a["candidates"] >= len(ids)
    children = {}
    for sp in t["spans"][1:]:
        assert sp["parent_id"] == root["span_id"], sp["name"]
        n, s = children.get(sp["name"], (0, 0.0))
        children[sp["name"]] = (n + 1, s + sp["dur_s"])
    assert set(children) == set(stages)
    for name, (n, s) in stages.items():
        assert children[name][0] == n, name
        assert children[name][1] == pytest.approx(s, abs=1e-8 * n), name


def test_stages_add_up_to_no_more_than_the_lookup(world):
    """(d): the stages of one lookup lie inside its ``client.lookup``."""
    path, c, ctx, cs, user, repo = world
    for call in (
        lambda: list(c.lookup_resources(ctx, cs, "repo#read", user)),
        lambda: list(c.lookup_subjects(ctx, cs, f"repo:{repo}", "read", "user")),
    ):
        before = timers(*ALL, "client.lookup")
        call()
        got = gained(before, timers(*ALL, "client.lookup"))
        n, whole = got.pop("client.lookup")
        assert n == 1
        assert 0.0 < sum(s for _n, s in got.values()) <= whole


def test_root_is_current_only_while_the_device_lookup_runs(world):
    """Never across a ``yield``: the caller's thread sees no span between
    two ids, and the trace ends with the last id."""
    path, c, ctx, cs, user, repo = world
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=8)
    it = c.lookup_resources(ctx, cs, "repo#read", user)
    first = next(it)
    assert trace.current() is trace.NOOP
    assert not [t for t in tr.traces() if t["name"] == "lookup"]
    rest = list(it)
    [t] = [t for t in tr.traces() if t["name"] == "lookup"]
    assert t["spans"][0]["attrs"]["ids"] == 1 + len(rest) > 0 and first


def test_unsampled_slow_lookup_keeps_a_root_only_trace(world):
    """The keep-slow tail rule, as ``check`` has it."""
    path, c, ctx, cs, user, repo = world
    tr = trace.configure(sample_rate=0.0, slow_threshold_s=0.0, capacity=8)
    n0 = trace.spans_created()
    ids = list(c.lookup_subjects(ctx, cs, f"repo:{repo}", "read", "user"))
    assert trace.spans_created() == n0
    [t] = [t for t in tr.traces() if t["name"] == "lookup"]
    assert t["tail_kept"] is True
    assert t["spans"][0]["attrs"] == {"kind": "subjects", "ids": len(ids),
                                      "tail_kept": True}
