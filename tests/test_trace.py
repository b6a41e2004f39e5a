"""Request-scoped tracing (utils/trace.py): span-tree coverage of the
check lifecycle (admission → dispatch → stage events), error attributes
on the shed/retry path, the zero-allocation no-op contract when sampling
is off, the keep-slow tail rule, and watch/write spans."""

import json
import threading
import time

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_admission_control,
    with_latency_mode,
)
from gochugaru_tpu.utils import metrics, trace
from gochugaru_tpu.utils.admission import AdmissionConfig
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import DeadlineExceededError, ShedError

SCHEMA = """
definition user {}
definition doc { relation reader: user  permission read = reader }
"""


@pytest.fixture(autouse=True)
def _trace_hygiene():
    """No test may leak an installed tracer into the next (the tracer is
    process-global by design, like the fault registry)."""
    trace.disable()
    yield
    trace.disable()


@pytest.fixture(scope="module")
def doc_client():
    c = new_tpu_evaluator(with_latency_mode())
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    for i in range(16):
        txn.create(rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i}"))
    c.write(ctx, txn)
    rs = [rel.must_from_triple(f"doc:d{i}", "read", f"user:u{i}") for i in range(8)]
    # warm: first dispatch compiles; the traced assertions below want a
    # warm (budget-recording) latency dispatch
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    return c, ctx, rs


def _spans_by_name(t):
    out = {}
    for sp in t["spans"]:
        out.setdefault(sp["name"], []).append(sp)
    return out


def test_sampled_check_covers_admission_dispatch_stages(doc_client):
    c, ctx, rs = doc_client
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=32)
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    traces = [t for t in tr.traces() if t["name"] == "check"]
    assert len(traces) == 1, "one sampled check → exactly one trace"
    t = traces[0]
    by = _spans_by_name(t)

    # tree shape: check → dispatch → device.check_batch → latency.dispatch
    # → four stage spans
    root = by["check"][0]
    assert root["parent_id"] == -1 and root["attrs"]["batch"] == 8
    disp = by["dispatch"][0]
    assert disp["parent_id"] == root["span_id"]
    assert any(e["name"] == "admission.admit" for e in root["events"])
    dev = by["device.check_batch"][0]
    assert dev["parent_id"] == disp["span_id"]
    lat = by["latency.dispatch"][0]
    assert lat["parent_id"] == dev["span_id"]
    assert lat["attrs"]["compiled"] is False, "warm dispatch must not compile"
    stage_names = {"stage.host_lower", "stage.h2d", "stage.kernel", "stage.d2h"}
    assert stage_names <= set(by), set(by)
    for s in stage_names:
        assert by[s][0]["parent_id"] == lat["span_id"]

    # the stage span durations must agree with the metrics stage timers:
    # both are built from the SAME perf_counter stamps, so the last
    # budget's values match the span durations exactly (within the
    # float rounding the JSONL dump applies)
    engine = c._engine
    dsnap = next(iter(c._dsnap_cache.values()))
    b = dsnap.latency_path.last_budget
    for sname, bval in [
        ("stage.host_lower", b.host_lower_s), ("stage.h2d", b.h2d_s),
        ("stage.kernel", b.kernel_s), ("stage.d2h", b.d2h_s),
    ]:
        assert by[sname][0]["dur_s"] == pytest.approx(bval, abs=1e-9), sname
    assert lat["dur_s"] == pytest.approx(b.total_s, abs=1e-9)
    # ... and the metrics registry really did observe that kernel sample
    ring = metrics.default._samples.get("latency.kernel_s")
    assert ring and any(abs(v - b.kernel_s) < 1e-12 for v in ring)

    # the JSONL dump round-trips
    lines = [ln for ln in tr.dump_jsonl().splitlines() if ln]
    parsed = [json.loads(ln) for ln in lines]
    assert any(p["trace_id"] == t["trace_id"] for p in parsed)


def test_shed_retry_path_records_shed_error():
    c = new_tpu_evaluator(
        with_latency_mode(),
        with_admission_control(AdmissionConfig(max_inflight=1)),
    )
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    txn.create(rel.must_from_triple("doc:d0", "reader", "user:u0"))
    c.write(ctx, txn)
    r = rel.must_from_triple("doc:d0", "read", "user:u0")
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=32)

    # occupy the single admission slot so every dispatch sheds
    cm = c._admission.gate.admit()
    cm.__enter__()
    try:
        with pytest.raises((DeadlineExceededError, ShedError)):
            c.check(ctx.with_timeout(0.30), consistency.full(), r)
    finally:
        cm.__exit__(None, None, None)

    traces = [t for t in tr.traces() if t["name"] == "check"]
    assert traces, "shed check must still finish (and keep) its trace"
    t = traces[-1]
    root = t["spans"][0]
    # the ShedError lands as a root attribute (set by the gate) ...
    assert root["attrs"].get("shed_error") == "ShedError"
    # ... as admission.shed events ...
    evs = [e for sp in t["spans"] for e in sp.get("events", ())]
    assert any(
        e["name"] == "admission.shed" and e.get("error") == "ShedError"
        for e in evs
    )
    # ... and the retry envelope recorded at least one backoff on it
    assert any(
        e["name"] == "retry" and e.get("error") == "ShedError" for e in evs
    )
    # the terminal error is attributed on the root
    assert root["attrs"].get("error") in ("DeadlineExceededError", "ShedError")


def test_sampling_off_allocates_zero_spans(doc_client):
    c, ctx, rs = doc_client
    # rate 0: tracer installed but every head decision is "no"
    trace.configure(sample_rate=0.0, slow_threshold_s=None)
    assert trace.root_span("check") is trace.NOOP
    n0 = trace.spans_created()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    assert trace.spans_created() == n0, (
        "sampling off must allocate no Span objects anywhere on the path"
    )
    # tracer absent entirely: same contract, and the context rides free
    trace.disable()
    ctx2 = ctx.with_span(trace.NOOP)
    assert ctx2 is ctx, "NOOP span must not grow the context chain"
    assert ctx.span() is trace.NOOP
    n0 = trace.spans_created()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    assert trace.spans_created() == n0


def test_keep_slow_tail_rule(doc_client):
    c, ctx, rs = doc_client
    # head sampling off, tail threshold 0 → every request is "slow"
    tr = trace.configure(sample_rate=0.0, slow_threshold_s=0.0, capacity=8)
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    kept = [t for t in tr.traces() if t["name"] == "check"]
    assert kept and kept[-1]["tail_kept"] is True
    assert kept[-1]["spans"][0]["attrs"]["batch"] == 8
    assert kept[-1]["duration_s"] > 0
    # and a high threshold keeps nothing
    tr = trace.configure(sample_rate=0.0, slow_threshold_s=60.0)
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    assert not tr.traces()


def test_watch_and_write_spans(doc_client):
    c, ctx, _ = doc_client
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=32)
    wctx = ctx.with_cancel()
    from gochugaru_tpu.rel.update import UpdateFilter

    stream = c.updates_since_revision(wctx, UpdateFilter(), "")
    got = []

    def consume():  # exactly one update, then the thread exits
        try:
            got.append(next(stream))
        except StopIteration:
            pass

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        txn = rel.Txn()
        txn.touch(rel.must_from_triple("doc:d0", "reader", "user:watcher"))
        c.write(ctx, txn)
        t.join(timeout=10)
        assert not t.is_alive() and len(got) == 1
    finally:
        wctx.cancel()
        t.join(timeout=5)
        stream.close()
    names = {t_["name"] for t_ in tr.traces()}
    assert "write" in names, names
    assert "watch" in names, names
    watch = [t_ for t_ in tr.traces() if t_["name"] == "watch"][-1]
    assert watch["spans"][0]["attrs"]["delivered"] == 1
    write = [t_ for t_ in tr.traces() if t_["name"] == "write"][-1]
    assert write["spans"][0]["attrs"]["applied"] == 1
    assert "revision" in write["spans"][0]["attrs"]


def test_span_event_cap_bounded():
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=4)
    sp = trace.root_span("flood")
    for i in range(trace.MAX_EVENTS + 50):
        sp.event("e", i=i)
    sp.end()
    t = tr.traces()[-1]
    root = t["spans"][0]
    assert len(root["events"]) == trace.MAX_EVENTS
    assert root["attrs"]["events_dropped"] == 50
    # the ring itself is bounded too
    for i in range(10):
        trace.root_span("r", i=i).end()
    assert len(tr.traces()) == 4


# ---------------------------------------------------------------------------
# stages: one primitive at every boundary of the check path
# ---------------------------------------------------------------------------

BATCH_STAGES = (
    "client.snapshot", "engine.lower", "engine.enqueue", "engine.fetch",
    "client.verdicts",
)


@pytest.fixture(scope="module")
def batch_client():
    """No latency mode: a check takes the batch path of check_batch
    (lower → enqueue → fetch)."""
    c = new_tpu_evaluator()
    ctx = background()
    c.write_schema(ctx, SCHEMA)
    txn = rel.Txn()
    for i in range(16):
        txn.create(rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i}"))
    c.write(ctx, txn)
    rs = [rel.must_from_triple(f"doc:d{i}", "read", f"user:u{i}") for i in range(8)]
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8  # warm
    return c, ctx, rs


def _counts(*timers):
    snap = metrics.default.snapshot()
    return {t: snap.get(f"{t}.count", 0) for t in timers}


def profiled_stage_events(trace_dir):
    """[(thread line, name, start_ns, end_ns, stats)] of the program's
    ``gochugaru.*`` events in the newest ``.xplane.pb`` under
    ``trace_dir`` (also used by tests/test_client_caching.py)."""
    import glob
    import os
    import warnings

    import jax

    found = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    assert found, "the profiler session left no .xplane.pb"
    profile = jax.profiler.ProfileData.from_file(found[-1])
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in profile.planes:
            for n, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("gochugaru."):
                        out.append((
                            (plane.name, n), e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats),
                        ))
    return out


def stage_names_on_thread_of(events, name):
    """Names, in time order, of the stages on the thread that ran
    ``name`` (other tests' idle serving threads may be annotating too)."""
    thread = next(e[0] for e in events if e[1] == name)
    return [e[1] for e in sorted(events, key=lambda e: e[2]) if e[0] == thread]


def assert_stages_are_leaves(events):
    """No ``gochugaru.*`` event may enclose (or overlap) another on its
    thread: a reduction that names a gap by the longest-overlapping span
    would hand every gap to the enclosing one."""
    by_thread = {}
    for thread, name, start, end, _stats in events:
        by_thread.setdefault(thread, []).append((start, end, name))
    for thread, evs in by_thread.items():
        evs.sort()
        for (s0, e0, n0), (s1, _e1, n1) in zip(evs, evs[1:]):
            assert s1 >= e0, f"{n1} starts inside {n0} on {thread}"


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records constructions."""

    built: list = []
    live = False

    def __init__(self, name, **kwargs):
        self.built.append(name)

    @classmethod
    def is_enabled(cls):
        return cls.live

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kwargs):
        pass


def test_stages_off_path_builds_no_span_and_no_annotation(
        batch_client, monkeypatch):
    """(a) No tracer, no profiler session: a full client.check builds no
    Span and no TraceAnnotation, yet every stage's timer gained exactly
    one sample."""
    c, ctx, rs = batch_client
    _CountingAnnotation.built, _CountingAnnotation.live = [], False
    monkeypatch.setattr(trace, "_ANNOTATION", _CountingAnnotation)
    # the batch's one interner call is timed always, like a stage
    timers = [f"{s}_s" for s in BATCH_STAGES] + ["engine.intern_s"]
    recorded = ("engine.lower_cpu_s",)
    before, n0 = _counts(*timers, *recorded), trace.spans_created()
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    after = _counts(*timers, *recorded)
    assert trace.spans_created() == n0
    assert _CountingAnnotation.built == []
    for t in timers:
        assert after[t] == before[t] + 1, t
    # the thread's CPU clock (a system call) is read only while
    # something records
    for t in recorded:
        assert after[t] == before[t], t
    # ... and with a session live each stage holds exactly one annotation
    _CountingAnnotation.live = True
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    mine = [f"gochugaru.{s}" for s in BATCH_STAGES]  # idle serving threads
    assert [n for n in _CountingAnnotation.built if n in mine] == mine  # may add theirs
    live = _counts("engine.intern_s", *recorded)
    for t in ("engine.intern_s", *recorded):
        assert live[t] == after[t] + 1, t


def test_stages_land_in_the_profilers_trace_as_leaves(batch_client, tmp_path):
    """(b) Under any jax.profiler session the stages are events of the
    same .xplane.pb, none encloses another on its thread, and the time
    inside the interner is observed once a batch — inside the session
    and outside it alike."""
    import jax

    c, ctx, rs = batch_client
    before = _counts("engine.intern_s", "engine.lower_s")
    with jax.profiler.trace(str(tmp_path)):
        assert c.check(ctx, consistency.full(), *rs) == [True] * 8
        with c.with_serving(cs=consistency.full()) as h:
            assert h.check(ctx, *rs) == [True] * 8
    inside = _counts("engine.intern_s", "engine.lower_s")
    assert inside["engine.intern_s"] == before["engine.intern_s"] + 2
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    outside = _counts("engine.intern_s", "engine.lower_s")
    assert outside["engine.intern_s"] == inside["engine.intern_s"] + 1
    # one observation a lowered batch, whoever records
    assert (outside["engine.intern_s"] - before["engine.intern_s"]
            == outside["engine.lower_s"] - before["engine.lower_s"])
    events = profiled_stage_events(tmp_path)
    names = {e[1] for e in events}
    want = {f"gochugaru.{s}" for s in BATCH_STAGES} | {
        "gochugaru.serve.form", "gochugaru.serve.concat",
        "gochugaru.serve.settle", "gochugaru.serve.idle",
    }
    assert want <= names, want - names
    # timer-only stages never become annotations
    assert not names & {"gochugaru.serve.wake", "gochugaru.serve.formed_wait",
                        "gochugaru.host.gc"}
    assert_stages_are_leaves(events)
    lower = [e for e in events if e[1] == "gochugaru.engine.lower"]
    assert all(e[4].get("batch") == 8 and "intern_s" in e[4] for e in lower)


def test_latency_stage_annotations_are_leaves(doc_client, tmp_path):
    """The latency path's four budget stages are annotated too, after
    ``engine.lower`` has closed."""
    import jax

    c, ctx, rs = doc_client
    with jax.profiler.trace(str(tmp_path)):
        assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    events = profiled_stage_events(tmp_path)
    names = stage_names_on_thread_of(events, "gochugaru.engine.lower")
    assert names == [f"gochugaru.{s}" for s in (
        "client.snapshot", "engine.lower", "engine.latency.fill",
        "engine.latency.h2d", "engine.latency.kernel", "engine.latency.d2h",
        "client.verdicts")], names
    assert_stages_are_leaves(events)


@pytest.mark.parametrize("entry", ["check", "submit_columns"])
def test_cache_layer_stages_are_leaves_around_the_direct_call(
        batch_client, tmp_path, entry):
    """The cache layer of a served batch is two stages around the direct
    evaluation, enclosing none of its stages: ``client.cache_read`` on
    every batch that enters the layer, ``client.cache_write`` only where
    something was left to evaluate.  The cache-off, dedup-off path
    (``Client.check`` above) has neither."""
    import jax

    from gochugaru_tpu.client import with_store
    from gochugaru_tpu.engine import vcache

    c, ctx, rs = batch_client
    timers = ("client.cache_read_s", "client.cache_write_s")
    t0 = _counts(*timers)
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    assert _counts(*timers) == t0  # the early return: no cache stage
    cached = new_tpu_evaluator(with_store(c.store))
    snap = c.store.snapshot_for(consistency.full())
    look = snap.interner.lookup
    cols = (np.array([look("doc", r.resource_id) for r in rs], np.int32),
            np.full(8, snap.compiled.slot_of_name["read"], np.int32),
            np.array([look("user", r.subject_id) for r in rs], np.int32))
    with jax.profiler.trace(str(tmp_path)):
        with cached.with_serving(
                cs=consistency.min_latency(),
                cache=vcache.VerdictCache(registry=metrics.Metrics())) as h:
            for _ in range(2):  # evaluated, then answered by the cache
                got = (h.check(ctx, *rs) if entry == "check" else
                       h.submit_columns(ctx, *cols).result(timeout=60.0))
                assert list(got) == [True] * 8
    t1 = _counts(*timers)
    assert t1["client.cache_read_s"] == t0["client.cache_read_s"] + 2
    assert t1["client.cache_write_s"] == t0["client.cache_write_s"] + 1
    events = profiled_stage_events(tmp_path)
    names = stage_names_on_thread_of(events, "gochugaru.client.cache_write")
    mine = [n for n in names if n.startswith(
        ("gochugaru.client.", "gochugaru.engine."))]
    assert mine[0] == "gochugaru.client.cache_read"
    assert mine[1] == "gochugaru.client.snapshot"
    assert mine[-2:] == ["gochugaru.client.cache_write",
                         "gochugaru.client.cache_read"], mine
    assert_stages_are_leaves(events)


def test_sampled_batch_path_stage_spans_equal_timer_samples(batch_client):
    """(c) A sampled request's stage child spans are built from the same
    two stamps as the timers: duration == timer sample, exactly."""
    c, ctx, rs = batch_client
    tr = trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=32)
    before = _counts("engine.intern_s")
    assert c.check(ctx, consistency.full(), *rs) == [True] * 8
    t = [t for t in tr.traces() if t["name"] == "check"][-1]
    by = _spans_by_name(t)
    dev = by["device.check_batch"][0]
    for s in BATCH_STAGES:
        sp = by[s][0]
        ring = metrics.default._samples[f"{s}_s"]
        assert any(abs(v - sp["dur_s"]) < 1e-9 for v in ring), s
        parent = dev if s.startswith("engine.") else by["dispatch"][0]
        assert sp["parent_id"] == parent["span_id"], s
    # the interner's one observation of the batch is the span's attribute
    assert _counts("engine.intern_s")["engine.intern_s"] == (
        before["engine.intern_s"] + 1)
    attrs = by["engine.lower"][0]["attrs"]
    assert attrs["batch"] == 8 and "memo_hits" not in attrs
    ring = metrics.default._samples["engine.intern_s"]
    assert any(abs(v - attrs["intern_s"]) < 1e-6 for v in ring)


@pytest.mark.parametrize("n_checks", [1, 40, 300])
def test_intern_counters_one_native_call_a_batch(batch_client, n_checks):
    """(e) A batch of B checks reaches the interner once, with 2·B keys
    (one resource and one subject a check): ``intern.batch_calls`` moves
    by 1 and ``intern.lookups`` by 2·B, so keys per call is B-scale."""
    c, ctx, _ = batch_client
    m = metrics.default
    rs = [rel.must_from_triple(f"doc:d{i % 16}", "read", f"user:x{i}")
          for i in range(n_checks)]
    keys = ("intern.lookups", "intern.batch_calls")
    before = {k: m.counter(k) for k in keys}
    c.check(ctx, consistency.full(), *rs)
    after = {k: m.counter(k) for k in keys}
    assert after["intern.batch_calls"] - before["intern.batch_calls"] == 1
    assert after["intern.lookups"] - before["intern.lookups"] == 2 * n_checks
    snap = m.snapshot()
    assert not [k for k in snap if "memo" in k], "the memo's counters are gone"


def test_stage_cpu_time_never_exceeds_wall_time(batch_client):
    """(f) ``<name>_cpu_s`` is read inside the wall stamps, while the
    stage records: for every sample CPU <= wall, and a stage that sleeps
    is mostly off-CPU."""
    reg = metrics.Metrics()
    with trace.stage("t.off", cpu=True, registry=reg):
        pass
    assert "t.off_cpu_s" not in reg._samples  # nothing records: no clock read
    trace.configure(sample_rate=1.0, slow_threshold_s=None, capacity=4)
    root = trace.root_span("t")
    x = 0
    for i in range(300):
        with trace.stage("t.busy", root, cpu=True, registry=reg):
            for j in range(i % 40):
                x += j
    with trace.stage("t.asleep", root, cpu=True, registry=reg):
        time.sleep(0.02)
    root.end()
    wall, cpu = reg._samples["t.busy_s"], reg._samples["t.busy_cpu_s"]
    assert len(wall) == len(cpu) == 300
    assert all(c_ <= w for w, c_ in zip(wall, cpu))
    assert reg._samples["t.asleep_cpu_s"][0] < 0.5 * reg._samples["t.asleep_s"][0]
    # through the engine: the batch's lowering, summed over a few checks
    c, ctx, rs = batch_client
    snap0 = metrics.default.snapshot()
    for _ in range(5):
        c.check(ctx, consistency.full(), *rs)
    snap1 = metrics.default.snapshot()
    d = lambda k: snap1[k] - snap0.get(k, 0)
    assert d("engine.lower_cpu_s.count") == d("engine.lower_s.count") == 5
    assert d("engine.lower_cpu_s.total_s") <= d("engine.lower_s.total_s")


def test_gc_timer_observes_full_collections_only():
    """``host.gc_s`` gains one sample a generation-2 collection.  The
    collector can start inside the registry's own lock, on the thread
    holding it: then the callback must not block, and the sample reaches
    the registry with the next stage that closes."""
    import gc

    new_tpu_evaluator()  # the first client installs the hook, once
    assert gc.callbacks.count(trace._on_gc) == 1
    new_tpu_evaluator()
    assert gc.callbacks.count(trace._on_gc) == 1
    count = lambda: _counts("host.gc_s")["host.gc_s"]
    n0 = count()
    gc.collect(0)
    gc.collect(1)
    assert count() == n0
    gc.collect()
    assert count() >= n0 + 1
    n1 = count()
    with metrics.default._lock:  # as if an allocation in observe() started it
        gc.collect()  # returns: the callback did not wait for the lock
        assert metrics.default._timings["host.gc_s"][0] == n1
    with trace.stage("t.drain", registry=metrics.Metrics()):
        pass
    assert count() >= n1 + 1 and not trace._GC_PENDING
