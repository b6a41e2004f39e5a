"""Flight recorder + trigger bus (utils/trace.py): always-on retention
at full fidelity regardless of the head sample, anomaly triggers →
incident bundles (breaker trip, shed spike, watch resume storm, pinned-
path recompile), cooldown rate-limiting, and the zero-configuration
end-to-end loop through ``with_telemetry(incident_dir=...)``."""

import json
import os
import threading
import time

import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_admission_control,
    with_latency_mode,
    with_telemetry,
)
from gochugaru_tpu.utils import faults, metrics, trace
from gochugaru_tpu.utils.admission import AdmissionConfig, CircuitBreaker
from gochugaru_tpu.utils.context import background

SCHEMA = """
definition user {}
definition doc { relation reader: user  permission read = reader }
"""


@pytest.fixture(autouse=True)
def _trace_hygiene():
    trace.disable()
    yield
    trace.disable()


def _recorder(**kw):
    kw.setdefault("grace_s", 0.0)
    kw.setdefault("cooldown_s", 0.0)
    return trace.install_recorder(trace.FlightRecorder(**kw))


def _doc_client(*opts):
    c = new_tpu_evaluator(with_latency_mode(), *opts)
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    for i in range(16):
        txn.create(rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i}"))
    c.write(ctx, txn)
    rs = [rel.must_from_triple(f"doc:d{i}", "read", f"user:u{i}")
          for i in range(8)]
    return c, ctx, rs


# ---------------------------------------------------------------------------
# always-on retention
# ---------------------------------------------------------------------------


def test_flight_ring_retains_unsampled_at_full_fidelity():
    """sample_rate=0 head-drops every request from the export ring, but
    with a recorder installed the full span TREE still builds and lands
    in the flight ring — the 'regardless of the sample rate' contract."""
    tr = trace.configure(sample_rate=0.0, slow_threshold_s=None)
    rec = _recorder(capacity=8)
    c, ctx, rs = _doc_client()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    assert tr.traces() == [], "export ring must stay head-sampled"
    flight = [t for t in rec.traces() if t["name"] == "check"]
    assert flight, "flight ring retained nothing"
    t = flight[-1]
    assert t["flight_only"] is True
    names = {sp["name"] for sp in t["spans"]}
    # full fidelity: the dispatch subtree, not a root-only stub
    assert {"check", "dispatch"} <= names
    assert metrics.default.counter("trace.flight_kept") > 0


def test_flight_ring_bounded_and_sampled_traces_ride_both_rings():
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=64)
    rec = _recorder(capacity=4)
    for i in range(10):
        trace.root_span("probe", i=i).end()
    assert len(rec.traces()) == 4  # ring bound
    assert [t["spans"][0]["attrs"]["i"] for t in rec.traces()] == [6, 7, 8, 9]
    assert len([t for t in tr.traces() if t["name"] == "probe"]) == 10
    assert all("flight_only" not in t for t in rec.traces())


def test_flight_only_slow_trace_exports_full_tree():
    """A flight-only trace that blows the slow threshold exports its
    FULL tree to /traces — strictly better than the root-only tail-kept
    stub the recorder-less path produces."""
    tr = trace.configure(sample_rate=0.0, slow_threshold_s=0.0)
    _recorder()
    sp = trace.root_span("check", batch=1)
    sp.child("dispatch").end()
    sp.end()
    kept = tr.traces()
    assert len(kept) == 1
    assert len(kept[0]["spans"]) == 2  # full tree, not root-only
    # the documented flag rides along: /traces consumers filtering on
    # tail_kept must see flight-only slow trees too
    assert kept[0]["tail_kept"] is True and kept[0]["flight_only"] is True
    assert metrics.default.counter("trace.tail_kept") > 0


def test_no_recorder_means_noop_unsampled_path():
    trace.configure(sample_rate=0.0, slow_threshold_s=None)
    n0 = trace.spans_created()
    assert trace.root_span("check") is trace.NOOP
    assert trace.spans_created() == n0


# ---------------------------------------------------------------------------
# the trigger bus
# ---------------------------------------------------------------------------


def test_trigger_captures_bundle_with_traces_metrics_context(tmp_path):
    m = metrics.Metrics()
    trace.configure(sample_rate=1.0, slow_threshold_s=None, registry=m)
    rec = _recorder(incident_dir=str(tmp_path), registry=m)
    rec.add_context("cost_model", lambda: {"overall_s": 0.001})
    rec.add_context("broken", lambda: 1 / 0)
    m.inc("checks.requested", 7)
    m.observe("checks.dispatch", 0.003)
    m.observe_hist("serve.request_latency", 0.02, (0.01, 0.1),
                   trace_id="tid-x")
    with trace.root_span("check", batch=2) as sp:
        sp.child("dispatch").set_attr("error", "UnavailableError").end()
    iid = trace.trigger_incident("breaker.trip", consecutive=3)
    assert iid is not None
    rec.flush()
    files = [f for f in os.listdir(tmp_path) if f.startswith("incident_")]
    assert len(files) == 1 and "breaker.trip" in files[0]
    lines = [json.loads(ln)
             for ln in (tmp_path / files[0]).read_text().splitlines()]
    head = lines[0]
    assert head["kind"] == "incident" and head["trigger"] == "breaker.trip"
    assert head["info"] == {"consecutive": 3}
    assert head["context"]["cost_model"] == {"overall_s": 0.001}
    # a broken provider records itself, never loses the bundle
    assert head["context"]["broken"] == {"provider_error": "ZeroDivisionError"}
    trs = [ln for ln in lines if ln["kind"] == "trace"]
    assert len(trs) == 1 and trs[0]["trace_id"] in head["trace_ids"]
    assert any("error" in (sp.get("attrs") or {})
               for sp in trs[0]["spans"])
    mt = next(ln for ln in lines if ln["kind"] == "metrics")
    assert mt["counters"]["checks.requested"] == 7
    assert "p99_s" in mt["timers"]["checks.dispatch"]
    hs = next(ln for ln in lines if ln["kind"] == "hists")
    assert hs["hists"]["serve.request_latency"]["exemplars"][1][0] == "tid-x"
    # the in-memory bundle serves identically (the /debug/incidents path)
    assert rec.bundle(iid) == (tmp_path / files[0]).read_text()
    idx = rec.incident_index()
    assert idx[-1]["state"] == "captured" and idx[-1]["traces"] == 1


def test_trigger_cooldown_rate_limits(tmp_path):
    m = metrics.Metrics()
    trace.configure(sample_rate=1.0, slow_threshold_s=None, registry=m)
    clock = [0.0]
    rec = trace.install_recorder(trace.FlightRecorder(
        incident_dir=str(tmp_path), grace_s=0.0, cooldown_s=30.0,
        registry=m, clock=lambda: clock[0],
    ))
    assert rec.trigger("breaker.trip") is not None
    assert rec.trigger("breaker.trip") is None  # suppressed
    assert m.counter("incidents.suppressed") == 1
    # a DIFFERENT trigger class is not suppressed
    assert rec.trigger("slo.burn") is not None
    clock[0] += 31.0
    assert rec.trigger("breaker.trip") is not None
    rec.flush()
    assert m.counter("incidents.captured") == 3


def test_note_spike_detector():
    m = metrics.Metrics()
    trace.configure(sample_rate=1.0, slow_threshold_s=None, registry=m)
    clock = [0.0]
    rec = trace.install_recorder(trace.FlightRecorder(
        grace_s=0.0, cooldown_s=0.0, registry=m,
        spike_threshold=5, spike_window_s=1.0, clock=lambda: clock[0],
    ))
    for _ in range(4):
        trace.note_anomaly("shed")
    assert not rec.incident_index()  # under threshold: no incident
    clock[0] += 2.0  # window expires — old notes must not count
    for _ in range(4):
        trace.note_anomaly("shed")
    assert not rec.incident_index()
    trace.note_anomaly("shed")  # 5th inside the window → spike
    rec.flush()
    idx = rec.incident_index()
    assert len(idx) == 1 and idx[0]["trigger"] == "shed.spike"
    assert idx[0]["info"]["count"] == 5


def test_trigger_freezes_ring_against_post_trigger_flood():
    """The freeze is synchronous: traces retained at trigger time must
    survive however much post-anomaly traffic floods the ring during
    the capture grace — they are the incident's evidence."""
    trace.configure(sample_rate=1.0, slow_threshold_s=None)
    rec = trace.install_recorder(trace.FlightRecorder(
        capacity=8, grace_s=0.2, cooldown_s=0.0,
    ))
    for i in range(8):
        trace.root_span("pre", i=i).end()
    assert rec.trigger("breaker.trip") is not None
    # flood: far more than the ring holds, all before the grace expires
    for i in range(100):
        trace.root_span("post", i=i).end()
    rec.flush()
    names = [t["name"] for t in
             [json.loads(ln) for ln in
              rec.bundle(rec.incident_index()[0]["id"]).splitlines()]
             if t["kind"] == "trace"]
    assert names.count("pre") == 8, names
    # late-finishing roots ride along AFTER the frozen evidence
    assert names.index("post") > names.index("pre")


def test_max_incidents_prunes_oldest_files(tmp_path):
    trace.configure(sample_rate=1.0, slow_threshold_s=None)
    rec = _recorder(incident_dir=str(tmp_path), max_incidents=2)
    for i in range(4):
        rec.trigger(f"t{i}")
        rec.flush()
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 2
    assert "t2" in files[0] and "t3" in files[1]


# ---------------------------------------------------------------------------
# anomaly-site wiring
# ---------------------------------------------------------------------------


def test_breaker_trip_fires_incident():
    m = metrics.Metrics()
    trace.configure(sample_rate=1.0, slow_threshold_s=None, registry=m)
    rec = _recorder(registry=m)
    br = CircuitBreaker(threshold=2, cooldown_s=10.0, registry=m)
    br.record_failure()
    assert not rec.incident_index()
    br.record_failure()  # trips
    rec.flush()
    idx = rec.incident_index()
    assert len(idx) == 1 and idx[0]["trigger"] == "breaker.trip"
    assert idx[0]["info"] == {"consecutive": 2, "threshold": 2}


def test_gate_shed_burst_fires_spike():
    from gochugaru_tpu.utils.admission import DispatchGate
    from gochugaru_tpu.utils.errors import ShedError

    m = metrics.Metrics()
    trace.configure(sample_rate=1.0, slow_threshold_s=None, registry=m)
    rec = trace.install_recorder(trace.FlightRecorder(
        grace_s=0.0, cooldown_s=0.0, registry=m, spike_threshold=8,
    ))
    gate = DispatchGate(max_inflight=1, registry=m)
    with gate.admit():
        for _ in range(8):
            with pytest.raises(ShedError):
                with gate.admit():
                    pass
    rec.flush()
    assert [i["trigger"] for i in rec.incident_index()] == ["shed.spike"]


def test_watch_resume_storm_fires_incident():
    trace.configure(sample_rate=1.0, slow_threshold_s=None)
    rec = _recorder()
    c, ctx, _ = _doc_client()
    from gochugaru_tpu.rel.update import UpdateFilter

    wctx = ctx.with_cancel()
    stream = c.updates_since_revision(wctx, UpdateFilter(), "")
    got = []

    def consume():
        try:
            got.append(next(stream))
        except StopIteration:
            pass

    # every delivery attempt faults for 8 consecutive resumes — storm
    # threshold — then the stream recovers and delivers
    faults.arm("watch.stream", times=c.WATCH_STORM_RESUMES)
    t = threading.Thread(target=consume, daemon=True)
    t.start()
    txn = rel.Txn()
    txn.create(rel.must_from_triple("doc:storm", "reader", "user:u0"))
    c.write(ctx, txn)
    t.join(timeout=30)
    wctx.cancel()
    assert got, "stream never recovered"
    rec.flush()
    storms = [i for i in rec.incident_index()
              if i["trigger"] == "watch.resume_storm"]
    assert len(storms) == 1
    assert storms[0]["info"]["no_progress"] == c.WATCH_STORM_RESUMES


def test_latency_retrace_detection_fires_incident():
    """A fresh compile for a (slots, tier, qctx) combo this path already
    served warm means a pinned executable was LOST — the runtime alarm
    for the no-retrace invariant.  Forced here by evicting the pin
    caches under the path."""
    trace.configure(sample_rate=1.0, slow_threshold_s=None)
    rec = _recorder()
    c, ctx, rs = _doc_client()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8  # warm
    engine = c._engine
    snap = c._store.snapshot_for(consistency.full())
    dsnap = c._dsnap_for(engine, snap)
    lp = engine.latency_path(dsnap)
    assert lp.dispatch_count > 0 and lp._served_keys
    with engine._latency_pins_lock:
        engine._latency_pins.clear()
    lp._local.clear()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8  # recompiles
    rec.flush()
    idx = [i for i in rec.incident_index()
           if i["trigger"] == "latency.retrace"]
    assert len(idx) == 1
    assert metrics.default.counter("latency.retraces") == 1.0


# ---------------------------------------------------------------------------
# end-to-end: the acceptance loop (zero config beyond incident_dir)
# ---------------------------------------------------------------------------


def test_fault_storm_produces_bundle_with_failing_dispatch_traces(tmp_path):
    """The ISSUE's acceptance criterion: armed chaos sites trip the
    breaker under traffic and an incident bundle appears — containing
    the failing dispatch spans — with no configuration beyond
    ``with_telemetry(incident_dir=...)``."""
    c, ctx, rs = _doc_client(
        with_admission_control(AdmissionConfig(
            breaker_threshold=2, breaker_cooldown_s=60.0,
        )),
        with_telemetry(port=0, incident_dir=str(tmp_path)),
    )
    grace = c.recorder.grace_s
    try:
        # zero-config wiring: tracer (0% head sample) + recorder + SLO
        assert trace.enabled() and trace.recorder() is c.recorder
        assert c.slo is not None and c.telemetry is not None
        assert c.check(ctx, consistency.full(), *rs) == [True] * 8  # warm
        # the tripped breaker reroutes the failing request onto the batch
        # path, whose program compiles on first use: with a cold compile
        # cache that outlasts the default 0.25 s capture grace and the
        # bundle is written before the failing root span has ended
        c.recorder.grace_s = 5.0
        faults.arm("latency.dispatch", times=2)
        # the retry envelope absorbs both injected faults; the second
        # consecutive failure trips the breaker mid-request
        assert c.check(ctx, consistency.full(), *rs) == [True] * 8
        assert metrics.default.counter("breaker.trips") >= 1
        deadline = time.time() + 20
        bundle = None
        while bundle is None and time.time() < deadline:
            c.recorder.flush()
            hits = [f for f in os.listdir(tmp_path)
                    if "breaker.trip" in f]
            if hits:
                bundle = tmp_path / hits[0]
                break
            time.sleep(0.1)
        assert bundle is not None, "no incident bundle appeared"
        lines = [json.loads(ln)
                 for ln in bundle.read_text().splitlines()]
        head = lines[0]
        traces = [ln for ln in lines if ln["kind"] == "trace"]
        offending = [
            t["trace_id"] for t in traces
            if any("error" in (sp.get("attrs") or {}) for sp in t["spans"])
        ]
        assert offending, "bundle lacks the failing dispatch traces"
        assert set(offending) <= set(head["trace_ids"])
        # providers are keyed per telemetry client on the shared
        # recorder (first client bare, later ones #N-suffixed)
        ctx_keys = head["context"]
        adm_key = next(k for k in ctx_keys if k.startswith("admission"))
        assert any(k.startswith("cost_model") for k in ctx_keys)
        assert ctx_keys[adm_key]["breaker_state"] == 2
    finally:
        c.recorder.grace_s = grace
        if c.slo is not None:
            c.slo.close()
        c.telemetry.close()


def test_with_telemetry_shares_one_slo_engine_and_overrides_incident_dir(
    tmp_path,
):
    """Two with_telemetry clients in one process must share ONE SLO
    engine (they write the same slo.* gauges — two evaluators would
    fight and double-fire breach edges), and a later explicit
    incident_dir must WIN over the shared recorder's earlier one."""
    from gochugaru_tpu.utils import slo as _slo

    c1 = new_tpu_evaluator(
        with_telemetry(port=0, incident_dir=str(tmp_path / "a"))
    )
    c2 = new_tpu_evaluator(
        with_telemetry(port=0, incident_dir=str(tmp_path / "b"))
    )
    try:
        assert c1.slo is c2.slo and c2.slo is _slo.get_engine()
        assert c1.recorder is c2.recorder
        # the later caller's explicit dir took over
        assert c2.recorder.incident_dir == str(tmp_path / "b")
        # each client's context providers coexist on the shared
        # recorder (suffixed keys) — c2 must not clobber c1's
        adm_keys = [k for k in c1.recorder._context
                    if k.startswith("admission")]
        assert len(adm_keys) == 2
        # slos=() DISABLES: the shared engine actually stops
        eng = c1.slo
        c3 = new_tpu_evaluator(with_telemetry(port=0, slos=()))
        try:
            assert c3.slo is None and _slo.get_engine() is None
            assert eng._stop.is_set(), "disable must close the engine"
            # ...and a closed engine clears its slo.* gauges (a stale
            # breached=1 would page forever on /metrics)
            from gochugaru_tpu.utils import metrics as _m

            assert not any(
                k.startswith("slo.") for k in _m.default._gauges
            )
        finally:
            c3.telemetry.close()
    finally:
        _slo.install_engine(None)
        c1.telemetry.close()
        c2.telemetry.close()
