"""Performance-attribution subsystem (gochugaru_tpu/utils/perf.py):
the gathered-bytes model's closure (per-level == per-table == total)
and recursion-depth coverage, cost_analysis capture at pin time plus
the graceful decline when a backend refuses it, pad-waste accounting,
the bandwidth microbench's fingerprint cache, the wall-time ledger's
priority attribution and its 100%±ε closure under a chaos soak (armed
``latency.dispatch``/``batcher.form`` faults — retry/backoff time
attributed, not lost), the /perf telemetry endpoint, and the
bench_compare direction registry for the new perf columns."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_host_only_evaluation,
    with_latency_mode,
    with_store,
)
from gochugaru_tpu.utils import faults, metrics, perf
from gochugaru_tpu.utils.context import background

CS = consistency.full()
EPOCH = 1_700_000_000_000_000


def _store_world():
    c = new_tpu_evaluator(with_latency_mode())
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition org { relation admin: user  relation member: user }
    definition repo {
        relation org: org
        relation reader: user
        permission admin = org->admin
        permission read = reader + admin + org->member
    }
    """)
    rng = np.random.default_rng(11)
    txn = rel.Txn()
    for i in range(150):
        txn.touch(rel.must_from_triple(
            f"repo:r{i}", "reader", f"user:u{rng.integers(40)}"
        ))
        txn.touch(rel.must_from_triple(f"repo:r{i}", "org", f"org:o{i % 4}"))
    for o in range(4):
        txn.touch(rel.must_from_triple(f"org:o{o}", "admin", f"user:u{o}"))
        txn.touch(rel.must_from_triple(
            f"org:o{o}", "member", f"user:u{o + 8}"
        ))
    c.write(ctx, txn)
    oracle = new_tpu_evaluator(with_host_only_evaluation(), with_store(c.store))
    return c, oracle


@pytest.fixture(scope="module")
def world():
    return _store_world()


def _dsnap_of(c):
    snap = c.store.snapshot_for(CS)
    eng = c._engine_for(snap)
    return eng, c._dsnap_for(eng, snap)


def _rand_checks(rng, n):
    return [
        rel.must_from_triple(
            f"repo:r{rng.integers(150)}", "read", f"user:u{rng.integers(40)}"
        )
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# gathered-bytes model
# ---------------------------------------------------------------------------

def test_bytes_model_closes_and_covers_levels(world):
    """total == Σ per_level == Σ per_table, and the arrow-bearing world
    contributes recursion levels BEYOND the root dispatch (the old
    est_bytes_per_check docstring admitted it excluded them)."""
    c, _ = world
    _, ds = _dsnap_of(c)
    model = perf.gathered_bytes_model(ds)
    assert model.total > 0
    assert abs(sum(model.per_level) - model.total) < 1e-6
    assert abs(sum(model.per_table.values()) - model.total) < 1e-6
    # repo->org arrows: deeper levels must be modeled (level 1+ nonzero)
    assert len(model.per_level) > 1 and model.per_level[1] > 0
    # every charged table is a real device array
    assert set(model.per_table) <= set(ds.arrays)


def test_common_delegates_to_ledger(world):
    """benchmarks/common keeps ONE implementation: the ledger's."""
    from benchmarks.common import est_bytes_per_check, table_bytes

    c, _ = world
    _, ds = _dsnap_of(c)
    assert est_bytes_per_check(ds) == perf.est_bytes_per_check(ds)
    assert table_bytes(ds) == perf.table_bytes(ds)
    assert table_bytes(ds) == sum(
        int(getattr(v, "nbytes", 0)) for v in ds.arrays.values()
    )


def test_model_published_at_prepare(world):
    c, _ = world
    _, ds = _dsnap_of(c)
    perf.publish_model(ds)
    m = metrics.default
    assert m.gauge("perf.bytes_per_check") == perf.est_bytes_per_check(ds)
    assert m.gauge("perf.bytes_per_check.level0") > 0
    assert perf.last_model() is not None


# ---------------------------------------------------------------------------
# cost ledger
# ---------------------------------------------------------------------------

class _FakeCompiled:
    """Stands in for jax.stages.Compiled across backend behaviors."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca

    def memory_analysis(self):
        raise RuntimeError("no memory stats either")


def test_record_cost_normalizes_backends():
    perf.reset_cost_ledger()
    e = perf.record_cost(
        "t", "list", _FakeCompiled([{"flops": 10.0, "bytes accessed": 4.0}])
    )
    assert e["flops"] == 10.0 and e["bytes_accessed"] == 4.0
    e = perf.record_cost("t", "dict", _FakeCompiled({"flops": 3.0}))
    assert e["flops"] == 3.0
    perf.reset_cost_ledger()


def test_cost_analysis_unavailable_degrades_to_meta_model(world):
    """Satellite regression: a backend whose cost_analysis returns None
    or raises must not error — the entry records 'unavailable', the
    ``perf.cost_analysis_unavailable`` gauge counts it, and the roofline
    columns still come from the meta model."""
    perf.reset_cost_ledger()
    m = metrics.default
    base = m.gauge("perf.cost_analysis_unavailable", 0.0)
    e1 = perf.record_cost("t", "none", _FakeCompiled(None))
    e2 = perf.record_cost("t", "raise", _FakeCompiled(RuntimeError("nope")))
    assert e1["unavailable"] and e2["unavailable"]
    assert m.gauge("perf.cost_analysis_unavailable") == base + 2
    # the meta model is untouched by the decline: roofline columns work
    c, _ = world
    _, ds = _dsnap_of(c)
    cols = perf.roofline_columns(1e6, dsnap=ds)
    assert cols["bytes_per_check"] > 0
    assert cols["achieved_gbps"] > 0
    assert cols["roofline_frac"] > 0
    perf.reset_cost_ledger()


def test_thunk_failure_is_graceful():
    """A lazy thunk that blows up on realization records an
    'unavailable' entry instead of breaking cost_entries()."""
    perf.reset_cost_ledger()

    def boom():
        raise RuntimeError("lowering exploded")

    perf.register_cost_thunk("t", "boom", boom)
    ents = perf.cost_entries(realize=True)
    hit = next(e for e in ents if e["key"] == "boom")
    assert hit["unavailable"] and "lowering exploded" in hit["error"]
    perf.reset_cost_ledger()


def test_latency_pin_captures_cost_and_pad(world):
    """A pinned-tier dispatch records its executable's cost analysis at
    pin time (free: the Compiled is in hand) and feeds the pad ledger
    live-vs-padded lanes."""
    c, _ = world
    eng, ds = _dsnap_of(c)
    lp = eng.latency_path(ds)
    m = metrics.default
    snap = c.store.snapshot_for(CS)
    it = snap.interner
    slot = snap.compiled.slot_of_name
    B = 33
    q_res = np.array([it.node("repo", f"r{i}") for i in range(B)], np.int32)
    q_perm = np.full(B, slot["read"], np.int32)
    q_subj = np.array([it.node("user", f"u{i % 40}") for i in range(B)],
                      np.int32)
    live0 = m.counter("perf.pad.live_lanes")
    total0 = m.counter("perf.pad.total_lanes")
    out = lp.dispatch_columns(q_res, q_perm, q_subj, now_us=EPOCH)
    assert out is not None
    pins = [e for e in perf.cost_entries() if e["kind"] == "latency_pin"]
    assert pins, "pin-time capture missing"
    assert all(e.get("flops") or e.get("unavailable") for e in pins)
    assert m.counter("perf.pad.live_lanes") - live0 == B
    assert m.counter("perf.pad.total_lanes") - total0 == lp.last_budget.tier
    stats = perf.pad_stats()
    assert 0 <= stats["pad_fraction"] < 1
    assert str(lp.last_budget.tier) in stats["per_tier"]


def test_batch_path_registers_lazy_thunk(world):
    """The throughput path registers a LAZY cost capture at kernel-cache
    time (no compile on the serving path) that realizes on demand."""
    c, _ = world
    eng, ds = _dsnap_of(c)
    perf.reset_cost_ledger()
    rng = np.random.default_rng(3)
    snap = c.store.snapshot_for(CS)
    it = snap.interner
    slot = snap.compiled.slot_of_name
    B = 64
    q_res = np.array([it.node("repo", f"r{i}") for i in range(B)], np.int32)
    q_perm = np.full(B, slot["read"], np.int32)
    q_subj = np.array(
        [it.node("user", f"u{rng.integers(40)}") for _ in range(B)], np.int32
    )
    eng.check_columns(ds, q_res, q_perm, q_subj, now_us=EPOCH)
    pend = [e for e in perf.cost_entries() if e["kind"] == "batch"]
    assert pend and pend[0].get("pending"), pend
    ents = perf.cost_entries(realize=True)
    got = [e for e in ents if e["kind"] == "batch"]
    assert got and not any(e.get("pending") for e in got)
    assert got[0].get("flops") or got[0].get("unavailable")
    perf.reset_cost_ledger()


# ---------------------------------------------------------------------------
# roofline meter
# ---------------------------------------------------------------------------

def test_bandwidth_cache_fingerprint(tmp_path, monkeypatch):
    """The microbench measures once per backend fingerprint; a second
    read serves the cached verdict, a refresh re-measures, a stale
    fingerprint re-measures."""
    p = tmp_path / "roofline.json"
    monkeypatch.setattr(perf, "ROOFLINE_CACHE_PATH", str(p))
    bw = perf.measure_bandwidth(size_mb=2, reps=2)
    assert bw["gbps"] > 0 and not bw["cached"]
    bw2 = perf.measure_bandwidth(size_mb=2, reps=2)
    assert bw2["cached"] and bw2["gbps"] == bw["gbps"]
    # stale fingerprint → the cached verdict no longer stands
    blob = json.loads(p.read_text())
    blob["fingerprint"] = "jaxlib=other;backend=tpu;kind=v6e;n=8"
    p.write_text(json.dumps(blob))
    bw3 = perf.measure_bandwidth(size_mb=2, reps=2)
    assert not bw3["cached"]
    assert metrics.default.gauge("perf.roofline_gbps") == bw3["gbps"]


def test_roofline_columns_math(tmp_path, monkeypatch):
    p = tmp_path / "roofline.json"
    monkeypatch.setattr(perf, "ROOFLINE_CACHE_PATH", str(p))
    perf.measure_bandwidth(size_mb=2, reps=2)
    cols = perf.roofline_columns(
        2_000_000.0, bytes_per_check=100.0, registry=metrics.Metrics()
    )
    assert cols["bytes_per_check"] == 100.0
    assert cols["bytes_accessed_per_check"] == 100.0
    assert cols["achieved_gbps"] == round(100.0 * 2e6 / 1e9, 3)
    assert cols["roofline_frac"] == round(
        cols["achieved_gbps"] / cols["roofline_gbps"], 4
    )


# ---------------------------------------------------------------------------
# wall-time ledger
# ---------------------------------------------------------------------------

def test_wall_attribution_priority_and_closure():
    """Synthetic intervals: overlap resolves by priority (kernel beats
    filter beats queue_wait), uncovered time is idle, and the buckets
    sum to the window EXACTLY — the closure property by construction."""
    w = perf.WallLedger()
    w.start()
    t0 = w.t_start
    # filter spans [0, 10]; kernel overlays [2, 5]; queue_wait [8, 14]
    w._report("filter", t0 + 0.0, t0 + 0.010)
    w._report("kernel", t0 + 0.002, t0 + 0.005)
    w._report("queue_wait", t0 + 0.008, t0 + 0.014)
    while time.perf_counter() < t0 + 0.016:
        time.sleep(0.001)
    res = w.stop()
    s = res["seconds"]
    assert abs(s["kernel"] - 0.003) < 1e-9
    assert abs(s["filter"] - 0.007) < 1e-9  # 10ms minus the kernel overlay
    assert abs(s["queue_wait"] - 0.004) < 1e-9  # [10, 14]: filter wins [8,10]
    assert s["idle"] > 0
    assert abs(sum(s.values()) - res["window_s"]) < 1e-4
    # closure comes from the UNROUNDED sums: exact by construction even
    # on a sub-100µs window (where µs-rounded bucket seconds would read
    # percent-level noise)
    assert res["closure_frac"] == 1.0
    assert 0 < res["named_frac"] < 1


def test_wall_report_noop_without_window():
    """No armed window → report_wall is a no-op (and cheap)."""
    assert perf._WALL is None
    perf.report_wall("kernel", 0.0, 1.0)  # must not raise or leak


def test_wall_interval_bound():
    w = perf.WallLedger()
    old = perf.WALL_INTERVAL_MAX
    try:
        perf.WALL_INTERVAL_MAX = 4
        w.start()
        t0 = w.t_start
        for i in range(10):
            w._report("filter", t0, t0 + 0.001)
        res = w.stop()
        assert res["intervals"] == 4 and res["dropped"] == 6
        assert res["closure_frac"] >= 0.99
    finally:
        perf.WALL_INTERVAL_MAX = old
        perf._WALL = None


def test_wall_ledger_closes_under_serving(world):
    """Real serving traffic: the window's buckets account ≈100% of wall
    time and the device stages appear (the bench9 row block's
    contract)."""
    c, oracle = world
    ctx = background()
    rng = np.random.default_rng(5)
    w = perf.WallLedger().start()
    with c.with_serving() as h:
        futs = []
        for k in range(48):
            futs.append(h.submit(ctx, *_rand_checks(rng, 8),
                                 client_id=k % 4))
        got = [f.result(timeout=60.0) for f in futs]
    res = w.stop()
    # closure is structural (idle is the residual) — the accounting's
    # teeth are zero drops + the expected named buckets being nonzero
    assert res["closure_frac"] >= 0.95, res
    assert res["dropped"] == 0, res
    assert res["named_frac"] > 0, res
    assert res["seconds"]["kernel"] > 0, res
    assert res["seconds"]["host_prep"] > 0, res
    assert perf.last_wall() is res or perf.last_wall() == res
    m = metrics.default
    assert m.gauge("perf.wall.closure_frac") >= 0.95
    # spot-check answers stayed correct under the window
    want = oracle.check(ctx, CS, *_rand_checks(np.random.default_rng(5), 8))
    assert len(want) == 8 and len(got) == 48


def test_wall_ledger_closure_under_chaos(world):
    """Satellite: with ``latency.dispatch`` and ``batcher.form`` armed
    at seeded probabilities the ledger STILL closes to 100%±ε, and the
    retry/backoff + form-retry time is attributed (nonzero buckets),
    not lost to idle."""
    c, oracle = world
    ctx = background()
    rng = np.random.default_rng(9)
    m = metrics.default
    r0 = m.counter("retry.retries")
    w = perf.WallLedger().start()
    with faults.default.armed("latency.dispatch", probability=0.25, seed=4), \
         faults.default.armed("batcher.form", probability=0.25, seed=5):
        with c.with_serving() as h:
            errors = []

            def worker(k):
                lr = np.random.default_rng(k)
                for _ in range(6):
                    qs = _rand_checks(lr, 5)
                    try:
                        got = h.check(ctx.with_timeout(60.0), *qs,
                                      client_id=k)
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                        return
                    want = oracle.check(ctx, CS, *qs)
                    if list(got) != list(want):
                        errors.append((got, want))

            ts = [threading.Thread(target=worker, args=(k,))
                  for k in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
    res = w.stop()
    assert not errors, errors[:3]
    assert res["closure_frac"] >= 0.95, res
    assert res["dropped"] == 0, res
    retried = m.counter("retry.retries") - r0
    assert retried > 0, "chaos never engaged the retry envelope"
    # attributed, not lost: the backoff pauses and the former's fault
    # retries show up as named buckets
    assert res["seconds"]["backoff"] > 0, res
    assert res["seconds"]["form"] > 0, res


# ---------------------------------------------------------------------------
# /perf endpoint + incident context
# ---------------------------------------------------------------------------

def test_perf_endpoint_serves_ledger(world, tmp_path, monkeypatch):
    from gochugaru_tpu.utils.telemetry import TelemetryServer

    monkeypatch.setattr(
        perf, "ROOFLINE_CACHE_PATH", str(tmp_path / "roofline.json")
    )
    c, _ = world
    _, ds = _dsnap_of(c)
    perf.publish_model(ds)
    srv = TelemetryServer(port=0)
    try:
        def get(path):
            with urllib.request.urlopen(srv.url + path, timeout=30) as r:
                return json.loads(r.read().decode())

        rep = get("/perf")
        assert rep["bytes_model"]["total"] == round(
            perf.est_bytes_per_check(ds), 1
        )
        assert rep["bytes_model"]["per_table"]
        assert "pad" in rep and "cost" in rep
        assert rep["roofline"] is None  # fresh cache path, no bench ask
        rep2 = get("/perf?bench=1")
        assert rep2["roofline"] and rep2["roofline"]["gbps"] > 0
        rep3 = get("/perf")  # now cached
        assert rep3["roofline"]["gbps"] == rep2["roofline"]["gbps"]
    finally:
        srv.close()


def test_context_state_is_cheap_and_complete(world):
    c, _ = world
    _, ds = _dsnap_of(c)
    perf.publish_model(ds)
    st = perf.context_state()
    assert st["bytes_per_check"] == round(perf.est_bytes_per_check(ds), 1)
    assert "pad" in st and "cost_entries" in st and "wall" in st
    json.dumps(st)  # bundle-serializable


# ---------------------------------------------------------------------------
# bench_compare direction registry (satellite)
# ---------------------------------------------------------------------------

def _bench_compare():
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "bench_compare.py",
    )
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_compare_perf_column_directions():
    bc = _bench_compare()
    # higher-is-better: a drop must read as regression
    assert not bc.lower_is_better("serve_openloop_goodput.roofline_frac", "")
    assert not bc.lower_is_better(
        "rbac_2hop_bulk_check_throughput.achieved_gbps", "checks/sec/chip"
    )
    # lower-is-better: pad share shrinking is the win
    assert bc.lower_is_better("serve_openloop_goodput.pad_fraction",
                              "checks/sec")
    # the perf columns are promoted off headline rows from round one
    for fld in ("achieved_gbps", "roofline_frac", "pad_fraction"):
        assert fld in bc._PROMOTED_FIELDS


def test_bench_compare_cache_column_directions():
    """The verdict-cache bench columns are direction-aware from round
    one: hit_rate/dedup_frac falling is a regression (same pattern as
    the PR-12 achieved_gbps fix — ``dedup_frac`` must not fall into any
    lower-better suffix bucket, and ``cache_hit_rate`` ends with
    ``hit_rate`` so headline and sweep rows both resolve)."""
    bc = _bench_compare()
    assert not bc.lower_is_better("serve_openloop_goodput.cache_hit_rate", "")
    assert not bc.lower_is_better("serve_cache_ab.hit_rate", "checks/sec")
    assert not bc.lower_is_better("serve_openloop_goodput.dedup_frac", "")
    assert not bc.lower_is_better("serve_cache_ab.cache_speedup", "x")
    assert "cache_hit_rate" in bc._PROMOTED_FIELDS
    # dedup_frac is direction-registered but deliberately NOT promoted
    # (workload-noise-sized absolute values would flap the trajectory)
    assert "dedup_frac" not in bc._PROMOTED_FIELDS
    # direction actually drives the verdict
    old = {"h.cache_hit_rate": {"value": 0.9, "unit": "", "platform": ""}}
    new = {"h.cache_hit_rate": {"value": 0.5, "unit": "", "platform": ""}}
    rows, regressions = bc.compare(old, new, "r01", "r02", 0.10)
    assert regressions == 1 and "REGRESSED" in "\n".join(rows)


def test_bench_compare_flags_roofline_regression():
    bc = _bench_compare()
    old = {
        "h.roofline_frac": {"value": 0.5, "unit": "checks/sec", "platform": ""},
        "h.pad_fraction": {"value": 0.5, "unit": "checks/sec", "platform": ""},
    }
    new = {
        "h.roofline_frac": {"value": 0.3, "unit": "checks/sec", "platform": ""},
        "h.pad_fraction": {"value": 0.3, "unit": "checks/sec", "platform": ""},
    }
    rows, regressions = bc.compare(old, new, "r01", "r02", 0.10)
    assert regressions == 1  # roofline_frac fell; pad_fraction improved
    table = "\n".join(rows)
    assert "REGRESSED" in table and "improved" in table


def test_bench_compare_extracts_promoted_perf_fields(tmp_path):
    bc = _bench_compare()
    doc = {"tail": json.dumps({
        "metric": "m", "value": 1.0, "unit": "checks/sec",
        "achieved_gbps": 1.5, "roofline_frac": 0.2, "pad_fraction": 0.1,
    })}
    p = tmp_path / "BENCH_r99.json"
    p.write_text(json.dumps(doc))
    got = bc.metrics_of(str(p))
    assert got["m.achieved_gbps"]["value"] == 1.5
    assert got["m.roofline_frac"]["value"] == 0.2
    assert got["m.pad_fraction"]["value"] == 0.1


def test_bench_compare_provenance_column_directions():
    """The decision-provenance columns are direction-aware from round
    one: explain_overhead_frac growing means the zero-cost contract is
    eroding, decisions_dropped growing is an audit-trail hole — both
    lower-better and promoted off headline rows (the PR-12/13 pattern)."""
    bc = _bench_compare()
    assert bc.lower_is_better("explain_smoke.explain_overhead_frac", "ok")
    assert bc.lower_is_better("explain_smoke.decisions_dropped", "ok")
    # the trailing "_frac" must not read as higher-better via the
    # roofline_frac rule
    assert not bc.lower_is_better("serve_openloop_goodput.roofline_frac", "")
    for fld in ("explain_overhead_frac", "decisions_dropped"):
        assert fld in bc._PROMOTED_FIELDS


def test_bench_compare_spmm_column_directions():
    """The fused-SpMM bench columns are direction-aware from round one:
    ``mixed_users_rate`` (bench8's 48-random-user candidate rate, the
    dispatch-floor workload the fused path exists for) falling is a
    regression; ``dispatches_per_lookup`` growing means the K-hop fusion
    is regressing to per-hop loops.  Both promoted off headline rows."""
    bc = _bench_compare()
    assert not bc.lower_is_better(
        "lookup_fused_vs_looped.mixed_users_rate", "x"
    )
    assert not bc.lower_is_better(
        "lookup_candidates_per_s.mixed_users_rate", "candidates/sec/chip"
    )
    assert bc.lower_is_better(
        "lookup_fused_vs_looped.dispatches_per_lookup", "x"
    )
    assert bc.lower_is_better(
        "lookup_candidates_per_s.dispatches_per_lookup",
        "candidates/sec/chip",
    )
    for fld in ("mixed_users_rate", "dispatches_per_lookup"):
        assert fld in bc._PROMOTED_FIELDS
    # direction actually drives the verdict both ways
    old = {
        "l.mixed_users_rate": {"value": 9e5, "unit": "x", "platform": ""},
        "l.dispatches_per_lookup": {"value": 1.0, "unit": "x",
                                    "platform": ""},
    }
    new = {
        "l.mixed_users_rate": {"value": 3e5, "unit": "x", "platform": ""},
        "l.dispatches_per_lookup": {"value": 3.9, "unit": "x",
                                    "platform": ""},
    }
    rows, regressions = bc.compare(old, new, "r05", "r06", 0.10)
    assert regressions == 2 and "REGRESSED" in "\n".join(rows)


def test_bench_compare_host_bound_escape():
    """A higher-better row measuring at its OWN host's bandwidth ceiling
    (``roofline_frac`` within tolerance of 1.0) flags ``host-bound``
    instead of failing: software can't beat the memory wall, so the
    round-over-round drop is the container, not the code.  Lower-better
    rows get no such escape, and a row below the ceiling still fails."""
    bc = _bench_compare()
    old = {"t": {"value": 12.6e6, "unit": "checks/sec/chip",
                 "platform": "cpu"}}
    at_ceiling = {"t": {"value": 5.8e6, "unit": "checks/sec/chip",
                        "platform": "cpu", "roofline_frac": 0.958}}
    rows, regressions = bc.compare(old, at_ceiling, "r05", "r06", 0.10)
    assert regressions == 0 and "host-bound" in "\n".join(rows)
    below_ceiling = {"t": {"value": 5.8e6, "unit": "checks/sec/chip",
                           "platform": "cpu", "roofline_frac": 0.55}}
    rows, regressions = bc.compare(old, below_ceiling, "r05", "r06", 0.10)
    assert regressions == 1 and "REGRESSED" in "\n".join(rows)
    # no escape for latency rows: at-ceiling bandwidth doesn't excuse a
    # p99 that tripled
    old_ms = {"t_p99_ms": {"value": 9.0, "unit": "ms", "platform": "cpu"}}
    new_ms = {"t_p99_ms": {"value": 30.0, "unit": "ms", "platform": "cpu",
                           "roofline_frac": 0.958}}
    rows, regressions = bc.compare(old_ms, new_ms, "r05", "r06", 0.10)
    assert regressions == 1
    # promoted companions inherit the parent row's roofline_frac
    import json as _json
    import tempfile, os as _os
    with tempfile.TemporaryDirectory() as td:
        p = _os.path.join(td, "BENCH_r06.json")
        row = {"metric": "m", "value": 1.0, "unit": "checks/sec/chip",
               "true_rate": 0.9, "roofline_frac": 0.97, "platform": "cpu"}
        with open(p, "w") as f:
            _json.dump({"tail": _json.dumps(row), "parsed": None}, f)
        mets = bc.metrics_of(p)
    assert mets["m"]["roofline_frac"] == 0.97
    assert mets["m.true_rate"]["roofline_frac"] == 0.97
