"""Continuous-batching serving front-end (gochugaru_tpu/serve/):
coalescing parity against the oracle, per-client fairness under a
zipf-heavy aggressor, deadline-aware flush vs the max-hold timer, the
no-retrace invariant across 100+ formed batches (reusing the
test_latency_path pin-reuse harness), breaker-trip re-forming onto the
batch path with zero lost/duplicated results, queue-depth shedding, the
shared cost model, and a chaos-soak round with the ``batcher.*`` fault
sites armed."""

import threading
import time

import numpy as np
import pytest

from gochugaru_tpu import consistency, rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_admission_control,
    with_host_only_evaluation,
    with_latency_mode,
    with_store,
)
from gochugaru_tpu.serve import MicroBatcher, ServeConfig
from gochugaru_tpu.utils import faults, metrics
from gochugaru_tpu.utils.admission import AdmissionConfig, CostModel
from gochugaru_tpu.utils.context import background
from gochugaru_tpu.utils.errors import (
    DeadlineExceededError,
    ShedError,
    UnavailableError,
)

from tests.test_latency_path import EPOCH, build_rbac_world

CS = consistency.full()


def _store_world():
    """Store-backed RBAC world + (latency client, oracle client)."""
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
    rng = np.random.default_rng(7)
    txn = rel.Txn()
    for i in range(120):
        txn.touch(rel.must_from_triple(
            f"repo:r{i}", "reader", f"user:u{rng.integers(60)}"
        ))
        txn.touch(rel.must_from_triple(f"repo:r{i}", "org", f"org:o{i % 3}"))
    for o in range(3):
        txn.touch(rel.must_from_triple(f"org:o{o}", "admin", f"user:u{o}"))
        txn.touch(rel.must_from_triple(
            f"org:o{o}", "member", f"user:u{o + 10}"
        ))
    c.write(ctx, txn)
    oracle = new_tpu_evaluator(with_host_only_evaluation(), with_store(c.store))
    return c, oracle


@pytest.fixture(scope="module")
def store_world():
    return _store_world()


def _rand_checks(rng, n):
    return [
        rel.must_from_triple(
            f"repo:r{rng.integers(120)}", "read", f"user:u{rng.integers(60)}"
        )
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# parity + coalescing
# ---------------------------------------------------------------------------

def test_serve_concurrent_parity_and_coalescing(store_world):
    """Concurrent submitters through the handle answer exactly like the
    host oracle, and the batcher genuinely coalesces (fewer formed
    batches than submissions)."""
    c, oracle = store_world
    ctx = background()
    m = metrics.default
    sub0 = m.counter("serve.submissions")
    bat0 = m.counter("serve.batches")
    errors = []
    with c.with_serving() as h:
        def worker(w):
            lr = np.random.default_rng(w)
            for _ in range(8):
                qs = _rand_checks(lr, 6)
                got = h.check(ctx, *qs, client_id=w)
                want = oracle.check(ctx, CS, *qs)
                if list(got) != list(want):
                    errors.append((w, got, want))
        ts = [threading.Thread(target=worker, args=(w,)) for w in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errors
    subs = m.counter("serve.submissions") - sub0
    bats = m.counter("serve.batches") - bat0
    assert subs == 48
    assert 0 < bats < subs, "no coalescing happened"


def test_serve_columns_parity(store_world):
    """The columnar surface answers like the engine's own columnar
    check (definite slice) and resolves the conditional slice."""
    c, oracle = store_world
    ctx = background()
    snap = c.store.snapshot_for(CS)
    inter = snap.interner
    slot = snap.compiled.slot_of_name
    rng = np.random.default_rng(3)
    B = 80
    q_res = np.array(
        [inter.node("repo", f"r{rng.integers(120)}") for _ in range(B)],
        np.int32,
    )
    q_perm = np.full(B, slot["read"], np.int32)
    q_subj = np.array(
        [inter.node("user", f"u{rng.integers(60)}") for _ in range(B)],
        np.int32,
    )
    with c.with_serving() as h:
        got = np.asarray(h.check_columns(ctx, q_res, q_perm, q_subj))
    want = [
        oracle.check(ctx, CS, rel.must_from_triple(
            f"repo:{inter.key_of(int(q_res[i]))[1]}", "read",
            f"user:{inter.key_of(int(q_subj[i]))[1]}",
        ))[0]
        for i in range(B)
    ]
    assert got.tolist() == want


def test_serve_over_partitioned_mesh():
    """The serving handle rides the partitioned mesh client too: the
    latency path declines sharded metas, so formed batches serve on the
    owner-routed throughput path — same answers."""
    from gochugaru_tpu.client import with_mesh
    from gochugaru_tpu.parallel import make_mesh

    c = new_tpu_evaluator(with_mesh(make_mesh(1, 4), partitioned=True))
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """)
    txn = rel.Txn()
    for i in range(60):
        txn.touch(rel.must_from_triple(
            f"doc:d{i}", "reader", f"user:u{i % 9}"
        ))
    c.write(ctx, txn)
    oracle = new_tpu_evaluator(
        with_host_only_evaluation(), with_store(c.store)
    )
    lr = np.random.default_rng(17)
    qs = [rel.must_from_triple(
        f"doc:d{lr.integers(60)}", "read", f"user:u{lr.integers(9)}"
    ) for _ in range(32)]
    with c.with_serving() as h:
        got = h.check(ctx.with_timeout(120.0), *qs)
    assert list(got) == list(oracle.check(ctx, CS, *qs))


# ---------------------------------------------------------------------------
# fairness
# ---------------------------------------------------------------------------

def test_fairness_zipf_aggressor_round_robin():
    """A bulk aggressor whose queued volume alone exceeds the formed
    batch cannot starve interactive clients: round-robin formation
    admits every client's head into the batch, while plain FIFO order
    would place the interactive submissions far past the cut.

    dedup=False pins the pre-dedup raw-count formation this test's cut
    arithmetic assumes (the aggressor's zipf%97 rows are duplicate-heavy
    — with dedup on they collapse into one batch by design; the dedup
    accounting has its own tests in test_vcache.py)."""
    b = MicroBatcher(
        tiers=(256, 1024, 4096), cost=CostModel(), start=False,
        registry=metrics.Metrics(), config=ServeConfig(dedup=False),
    )
    zipf = np.random.default_rng(1).zipf(1.3, 64 * 70)
    # the aggressor queues 70 CheckMany submissions of 64 first ...
    for i in range(70):
        cols = np.asarray(zipf[i * 64:(i + 1) * 64] % 97, np.int32)
        b.submit_columns("aggressor", cols, cols, cols)
    # ... then three interactive clients queue a single check each
    for w in range(3):
        one = np.zeros(1, np.int32)
        b.submit_columns(f"interactive{w}", one, one, one)
    assert b.depth == 70 * 64 + 3
    batch = b.form_batch()  # depth ≥ top tier → flushes 'full'
    assert batch.reason == "full"
    by_client = {}
    for s in batch.subs:
        by_client.setdefault(s.client_id, 0)
        by_client[s.client_id] += 1
    # every interactive client made it into THIS batch, despite being
    # submitted after 70×64 = 4480 aggressor checks (FIFO would need
    # the cut at 4483; the batch holds ≤ 4096)
    for w in range(3):
        assert by_client.get(f"interactive{w}") == 1, by_client
    assert by_client["aggressor"] >= 1  # aggressor still progresses
    assert b.depth > 0  # its tail is deferred, not lost
    b.close()


# ---------------------------------------------------------------------------
# deadline-aware hold-back
# ---------------------------------------------------------------------------

def test_deadline_flush_beats_maxhold():
    """With a long max-hold, a deadline-bearing submission flushes when
    its budget says waiting longer would miss it — far before the
    max-hold timer."""
    reg = metrics.Metrics()
    cost = CostModel()
    cost.observe(0.01, tier=256)  # "a tier-256 dispatch costs ~10 ms"
    done = threading.Event()

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        done.set()
        return np.zeros(q_res.shape[0], bool)

    b = MicroBatcher(
        tiers=(256, 1024, 4096), cost=cost, registry=reg,
        config=ServeConfig(hold_max_s=2.0),
        dispatch_cols=dispatch_cols,
    )
    try:
        ctx = background().with_timeout(0.25)
        t0 = time.perf_counter()
        one = np.zeros(1, np.int32)
        fut = b.submit_columns("c", one, one, one, ctx=ctx)
        out = fut.result(ctx, timeout=5.0)
        held = time.perf_counter() - t0
        assert out.shape == (1,)
        # flushed by the deadline rule, nowhere near the 2 s max-hold
        assert held < 1.0, f"held {held:.3f}s — deadline rule never fired"
        assert reg.counter("serve.flush_deadline") == 1
        assert reg.counter("serve.flush_maxhold") == 0
    finally:
        b.close()


def test_deadline_expired_in_queue_rejected():
    """A submission whose deadline passes while queued is rejected at
    formation (classified, retriable) instead of burning batch slots."""
    reg = metrics.Metrics()
    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), start=False, registry=reg,
        config=ServeConfig(hold_max_s=0.001),
    )
    ctx = background().with_timeout(0.005)
    one = np.zeros(1, np.int32)
    fut = b.submit_columns("c", one, one, one, ctx=ctx)
    time.sleep(0.02)  # deadline passes while "queued"
    batch = b.form_batch()
    assert batch.total == 0
    assert fut.done()
    with pytest.raises(DeadlineExceededError):
        fut.result()
    assert reg.counter("serve.deadline_expired") == 1
    b.close()


# ---------------------------------------------------------------------------
# queue-depth shed
# ---------------------------------------------------------------------------

def test_queue_depth_shed_raises_shederror():
    reg = metrics.Metrics()
    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), start=False, registry=reg,
        config=ServeConfig(queue_max=64),
    )
    cols = np.zeros(60, np.int32)
    b.submit_columns("a", cols, cols, cols)
    with pytest.raises(ShedError):
        b.submit_columns("b", cols[:8], cols[:8], cols[:8])
    assert reg.counter("serve.sheds") == 1
    # ShedError ⊂ UnavailableError: the retry envelope engages
    assert issubclass(ShedError, UnavailableError)
    b.close()


def test_close_rejects_undispatched():
    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), start=False,
        registry=metrics.Metrics(),
    )
    one = np.zeros(1, np.int32)
    fut = b.submit_columns("c", one, one, one)
    b.close()
    with pytest.raises(UnavailableError):
        fut.result()


# ---------------------------------------------------------------------------
# the moment a batch is formed: when the dispatcher can take it
# ---------------------------------------------------------------------------

_FLUSHES = ("full", "deadline", "maxhold", "drain")


class _HeldDispatch:
    """A ``dispatch_cols`` that records the resource column of every
    call and holds each of its first ``hold`` calls until ``step()``
    lets one through (``die`` makes the first held call raise a
    BaseException on its way out — a dispatcher crash)."""

    def __init__(self, hold=0, die=False):
        self.calls = []
        self.hold = hold
        self.die = die
        self._gate = threading.Semaphore(0)

    def __call__(self, q_res, q_perm, q_subj, latency, span):
        self.calls.append(q_res.tolist())
        if len(self.calls) <= self.hold:
            assert self._gate.acquire(timeout=10.0), "never released"
            if self.die:
                raise SystemExit("simulated dispatcher death")
        return np.zeros(q_res.shape[0], bool)

    def step(self):
        self._gate.release()

    def wait_calls(self, n):
        deadline = time.perf_counter() + 10.0
        while len(self.calls) < n:
            assert time.perf_counter() < deadline, (n, self.calls)
            time.sleep(0.001)


def _held_batcher(reg, held, **cfg):
    return MicroBatcher(
        tiers=(256, 1024), cost=CostModel(), registry=reg,
        config=ServeConfig(**cfg), dispatch_cols=held,
    )


def _submit_ids(b, ids, ctx=None):
    """One single-check submission an id, each from a client of its own
    (distinct rows: nothing parks on the in-flight singleflight)."""
    out = []
    for i in ids:
        col = np.full(1, i, np.int32)
        out.append(b.submit_columns(f"c{i}", col, col, col, ctx=ctx))
    return out


def _formed(reg):
    return sum(reg.counter(f"serve.flush_{r}") for r in _FLUSHES)


def test_backpressure_coalesces_queued_waves():
    """While a dispatch runs, later arrivals keep coalescing in the
    queues: wave one is dispatching and held, waves two and three
    arrive a hold-back apart behind it, and the batch formed when the
    dispatcher comes back holds waves two AND three (a hold-back-driven
    former froze wave two alone, and wave three behind it)."""
    reg = metrics.Metrics()
    held = _HeldDispatch(hold=1)
    b = _held_batcher(reg, held, hold_max_s=0.002)
    try:
        futs = _submit_ids(b, [1])
        held.wait_calls(1)
        for wave in (2, 3):
            futs += _submit_ids(b, range(wave * 10, wave * 10 + 3))
            # far past the hold-back: a former that flushed on its own
            # clock would have frozen this wave by now
            time.sleep(0.02)
            # nothing is formed behind the running batch — the wave is
            # still in the client queues, where the next one joins it
            assert _formed(reg) == len(held.calls) == 1
            assert b.depth == 3 * (wave - 1)
        held.step()
        for f in futs:
            assert f.result(timeout=10.0).shape == (1,)
    finally:
        b.close()
    assert len(held.calls) == 2, held.calls
    assert held.calls[0] == [1]
    assert sorted(held.calls[1]) == [20, 21, 22, 30, 31, 32]
    assert _formed(reg) == reg.counter("serve.batches") == 2
    assert reg.counter("serve.checks") == 7
    # batch one went out on an idle dispatcher's hold-back, batch two
    # the moment the dispatcher came back
    assert reg.counter("serve.formed_backlogged") == 1


def test_formed_backlogged_share_under_backpressure():
    """``serve.formed_backlogged`` counts the batches whose moment the
    dispatcher chose (their flush decision was already true when it
    came back): every batch behind a busy dispatcher, none that an idle
    one waited for — n busy cycles read (n - 1) / n."""
    reg = metrics.Metrics()
    held = _HeldDispatch(hold=4)
    b = _held_batcher(reg, held, hold_max_s=0.002)
    try:
        futs = _submit_ids(b, [1])
        held.wait_calls(1)
        for cycle in (2, 3, 4):
            futs += _submit_ids(b, [cycle])
            time.sleep(0.02)  # due, and waiting for the dispatcher
            held.step()
            held.wait_calls(cycle)
        held.step()
        for f in futs:
            f.result(timeout=10.0)
    finally:
        b.close()
    assert held.calls == [[1], [2], [3], [4]]
    assert reg.counter("serve.batches") == 4
    assert reg.counter("serve.formed_backlogged") == 3


@pytest.mark.parametrize("n, hold_max_s, reason", [
    (1, 0.05, "maxhold"),  # a lone request goes out on the hold-back
    (256, 2.0, "full"),    # a full tier goes out at once
])
def test_light_load_dispatch_moment_unchanged(n, hold_max_s, reason):
    """An idle dispatcher changes nothing: the flush decision alone
    picks the moment a partial batch goes out, and no batch counts as
    backlogged."""
    reg = metrics.Metrics()
    held = _HeldDispatch()
    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), registry=reg,
        config=ServeConfig(hold_max_s=hold_max_s), dispatch_cols=held,
    )
    try:
        time.sleep(0.1)  # the dispatcher is idle, blocked in formation
        cols = np.arange(n, dtype=np.int32)
        t0 = time.perf_counter()
        fut = b.submit_columns("c", cols, cols, cols)
        assert fut.result(timeout=10.0).shape == (n,)
        took = time.perf_counter() - t0
    finally:
        b.close()
    if reason == "maxhold":
        assert hold_max_s <= took < hold_max_s + 0.25, took
    else:
        assert took < 0.5, f"a full tier waited {took:.3f}s"
    assert reg.counter(f"serve.flush_{reason}") == _formed(reg) == 1
    assert reg.counter("serve.batches") == 1
    assert reg.counter("serve.formed_backlogged") == 0
    snap = reg.snapshot()
    assert snap["serve.formed_wait_s.count"] == 1  # one sample a batch
    assert snap["serve.idle_s.count"] >= 1


def test_deadline_passing_behind_busy_dispatcher_rejects_at_formation():
    """A deadline that passes while the dispatcher is busy is found at
    formation, when the dispatcher comes back: rejected there, and no
    lane of the next batch is spent on it."""
    reg = metrics.Metrics()
    held = _HeldDispatch(hold=1)
    b = _held_batcher(reg, held)
    try:
        first = _submit_ids(b, [1])
        held.wait_calls(1)
        dead = _submit_ids(b, [7], ctx=background().with_timeout(0.03))
        live = _submit_ids(b, [8])
        time.sleep(0.1)
        assert not dead[0].done()  # still queued: nobody formed it
        held.step()
        for f in first + live:
            f.result(timeout=10.0)
        with pytest.raises(DeadlineExceededError):
            dead[0].result(timeout=10.0)
    finally:
        b.close()
    assert held.calls == [[1], [8]]
    assert reg.counter("serve.deadline_expired") == 1
    assert reg.counter("serve.checks") == 2


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
@pytest.mark.parametrize("how", ["drains", "rejects", "dispatcher_dies"])
def test_close_behind_held_dispatch_strands_no_future(how):
    """close() while a dispatch is held, with work queued behind it: a
    dispatch that finishes inside ``drain_timeout_s`` lets the queue
    drain; one that does not gets its queue rejected at the timeout;
    and a dispatcher that dies on the way out rejects its own batch and
    the queue behind it.  No future is left unsettled."""
    reg = metrics.Metrics()
    held = _HeldDispatch(hold=1, die=how == "dispatcher_dies")
    b = _held_batcher(reg, held, drain_timeout_s=0.3 if how == "rejects"
                      else 10.0)
    first = _submit_ids(b, [1])
    held.wait_calls(1)
    queued = _submit_ids(b, [2, 3, 4])
    assert _formed(reg) == 1 and b.depth == 3
    t0 = time.perf_counter()
    if how == "rejects":
        b.close()  # the held dispatch outlasts the drain timeout
        took = time.perf_counter() - t0
        assert 0.3 <= took < 0.3 + 1.0, took
        for f in queued:
            with pytest.raises(UnavailableError):
                f.result(timeout=1.0)
        held.step()
        first[0].result(timeout=10.0)  # the dispatch itself completes
        assert len(held.calls) == 1
    elif how == "drains":
        closer = threading.Thread(target=b.close)
        closer.start()
        time.sleep(0.05)
        held.step()
        closer.join(timeout=10.0)
        assert not closer.is_alive()
        for f in first + queued:
            f.result(timeout=1.0)
        assert sorted(held.calls[1]) == [2, 3, 4]
        assert reg.counter("serve.flush_drain") == 1
    else:
        held.step()
        for f in first + queued:
            with pytest.raises(UnavailableError):
                f.result(timeout=10.0)
        assert reg.counter("serve.thread_crashes") == 1
        assert len(held.calls) == 1
    with pytest.raises(UnavailableError):
        _submit_ids(b, [9])
    assert all(f.done() for f in first + queued)
    b.close()


def test_form_fault_retried_with_no_request_lost():
    """``batcher.form`` fires before any dequeue: the dispatcher's
    formation pauses and retries, and every submission is dispatched
    exactly once."""
    reg = metrics.Metrics()
    held = _HeldDispatch()
    b = _held_batcher(reg, held)
    try:
        with faults.default.armed("batcher.form", times=3):
            futs = _submit_ids(b, range(40, 52))
            for f in futs:
                assert f.result(timeout=10.0).shape == (1,)
    finally:
        b.close()
    assert reg.counter("serve.form_faults") == 3
    assert sorted(i for c in held.calls for i in c) == list(range(40, 52))
    assert reg.counter("serve.checks") == 12


# ---------------------------------------------------------------------------
# no-retrace across formed batches (the pin-reuse harness)
# ---------------------------------------------------------------------------

def test_no_retrace_across_formed_batches():
    """100+ formed batches of varying occupancy through the pinned tier
    ladder pay ZERO XLA compiles after warmup — the continuous batcher
    inherits the latency path's no-retrace invariant by construction
    (every formed batch lands on a pinned pow2 tier shape)."""
    from gochugaru_tpu.engine.device import DeviceEngine

    cs, snap, users, repos, slot = build_rbac_world()
    engine = DeviceEngine(cs)
    dsnap = engine.prepare(snap)
    lp = engine.latency_path(dsnap)

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        out = None
        if latency:
            out = lp.dispatch_columns(q_res, q_perm, q_subj, now_us=EPOCH,
                                      span=span)
        if out is None:
            out = engine.check_columns(dsnap, q_res, q_perm, q_subj,
                                       now_us=EPOCH)
        d, p, ovf = out
        return np.asarray(d, bool)

    reg = metrics.Metrics()
    b = MicroBatcher(
        tiers=engine.config.latency_tiers, cost=CostModel(), registry=reg,
        config=ServeConfig(hold_max_s=0.0005),
        dispatch_cols=dispatch_cols,
    )
    rng = np.random.default_rng(23)
    try:
        # warm: one dispatch per perm subset the stream will use
        for perm in ("read", "admin"):
            B = 64
            q_res = rng.choice(repos, B).astype(np.int32)
            q_perm = np.full(B, slot[perm], np.int32)
            q_subj = rng.choice(users, B).astype(np.int32)
            b.submit_columns("warm", q_res, q_perm, q_subj).result(timeout=30)
        warm_compiles = lp.compile_count
        bat0 = reg.counter("serve.batches")
        for i in range(110):
            B = int(rng.integers(1, 200))
            q_res = rng.choice(repos, B).astype(np.int32)
            perm = "read" if i % 2 else "admin"
            q_perm = np.full(B, slot[perm], np.int32)
            q_subj = rng.choice(users, B).astype(np.int32)
            got = b.submit_columns("t", q_res, q_perm, q_subj).result(
                timeout=30
            )
            if i % 37 == 0:  # spot-check the coalesced answers stay right
                d, p, ovf = engine.check_columns(
                    dsnap, q_res, q_perm, q_subj, now_us=EPOCH
                )
                assert (np.asarray(got) == np.asarray(d, bool)).all()
        formed = reg.counter("serve.batches") - bat0
        assert formed >= 100
        assert lp.compile_count == warm_compiles, (
            f"batcher retraced: {lp.compile_count - warm_compiles} extra"
            f" compiles across {formed:.0f} formed batches"
        )
    finally:
        b.close()


# ---------------------------------------------------------------------------
# breaker trip mid-queue → re-form for the batch path (satellite fix)
# ---------------------------------------------------------------------------

def test_breaker_trip_midqueue_reforms_batch_path():
    """Trip the latency-path breaker while submissions are queued: the
    batcher's futures reject with classified errors, the envelopes
    re-submit, the breaker reroutes evaluation onto the batch path, and
    formation re-tiers (serve.reformed_batchpath) — with every answer
    still oracle-correct and no result lost or duplicated (a double
    future resolution raises by construction)."""
    c, oracle = (
        new_tpu_evaluator(
            with_latency_mode(),
            with_admission_control(AdmissionConfig(
                breaker_threshold=2, breaker_cooldown_s=120.0,
            )),
        ),
        None,
    )
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """)
    txn = rel.Txn()
    for i in range(40):
        txn.touch(rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i % 7}"))
    c.write(ctx, txn)
    oracle = new_tpu_evaluator(with_host_only_evaluation(), with_store(c.store))

    m = metrics.default
    lat0 = m.counter("latency.dispatches")
    results = {}
    errors = []
    with c.with_serving() as h:
        # wave 1 under an armed latency fault: enough consecutive
        # failures to trip threshold=2 while requests are queued
        with faults.default.armed("latency.dispatch", times=4):
            def worker(w):
                lr = np.random.default_rng(w)
                for j in range(6):
                    qs = [rel.must_from_triple(
                        f"doc:d{lr.integers(40)}", "read",
                        f"user:u{lr.integers(7)}",
                    ) for _ in range(3)]
                    try:
                        got = h.check(
                            ctx.with_timeout(30.0), *qs, client_id=w
                        )
                        results[(w, j)] = (qs, got)
                    except Exception as e:  # pragma: no cover
                        errors.append((w, j, e))
            ts = [threading.Thread(target=worker, args=(w,))
                  for w in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert not errors
        assert c._admission.breaker.state != 0, "breaker never tripped"
        # wave 2 with the breaker OPEN (120 s cooldown): formation must
        # re-tier for the batch path, and the pinned latency shapes
        # must NOT be replayed
        lat_open0 = m.counter("latency.dispatches")
        reform0 = m.counter("serve.reformed_batchpath")
        qs = _rand_docs_checks(12)
        got = h.check(ctx.with_timeout(30.0), *qs, client_id="wave2")
        results[("wave2", 0)] = (qs, got)
        assert m.counter("latency.dispatches") == lat_open0, (
            "pinned-tier shapes were replayed while the breaker was open"
        )
        assert m.counter("serve.reformed_batchpath") > reform0
    # zero lost: every submitted wave answered; zero wrong: oracle parity
    assert len(results) == 4 * 6 + 1
    for (w, j), (qs, got) in results.items():
        want = oracle.check(ctx, CS, *qs)
        assert list(got) == list(want), (w, j)
    assert m.counter("breaker.trips") >= 1


def _rand_docs_checks(n, seed=99):
    lr = np.random.default_rng(seed)
    return [rel.must_from_triple(
        f"doc:d{lr.integers(40)}", "read", f"user:u{lr.integers(7)}"
    ) for _ in range(n)]


# ---------------------------------------------------------------------------
# chaos soak with batcher.* sites armed
# ---------------------------------------------------------------------------

def test_chaos_soak_batcher_sites(store_world):
    """A soak round with ``batcher.form`` + ``batcher.dispatch`` +
    ``latency.dispatch`` armed at seeded probabilities: every coalesced
    answer still matches the oracle, nothing hangs, nothing is lost —
    form faults leave the queue intact, dispatch faults reject onto the
    submitters' retry envelopes."""
    c, oracle = store_world
    ctx = background()
    m = metrics.default
    inj0 = m.counter("faults.injected")
    errors = []
    with c.with_serving() as h:
        with faults.default.armed("batcher.form", probability=0.3,
                                  times=6, seed=101), \
             faults.default.armed("batcher.dispatch", probability=0.3,
                                  times=6, seed=102), \
             faults.default.armed("latency.dispatch", probability=0.15,
                                  times=4, seed=103):
            def worker(w):
                lr = np.random.default_rng(200 + w)
                for _ in range(8):
                    qs = _rand_checks(lr, 4)
                    got = h.check(
                        ctx.with_timeout(30.0), *qs, client_id=w
                    )
                    want = oracle.check(ctx, CS, *qs)
                    if list(got) != list(want):
                        errors.append((w, got, want))
            ts = [threading.Thread(target=worker, args=(w,))
                  for w in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    assert not errors
    assert m.counter("faults.injected") > inj0, "chaos round injected nothing"


# ---------------------------------------------------------------------------
# shared cost model (satellite fix) + histogram export
# ---------------------------------------------------------------------------

def test_cost_model_per_tier_shared():
    cm = CostModel(floor_s=0.0)
    assert not cm.has_samples()
    assert cm.expected_s() == 0.0
    cm.observe(0.010, tier=256)
    cm.observe(0.030, tier=1024)
    # tier-specific estimates; unseen tier falls back to the overall
    assert cm.expected_s(256) == pytest.approx(0.010)
    assert cm.expected_s(1024) == pytest.approx(0.030)
    assert cm.expected_s(4096) == cm.expected_s()
    overall = cm.expected_s()
    t256 = cm.expected_s(256)
    cm.decay()
    # decay targets the channel the tier-less shed read (here the
    # cheapest tier, 256) and leaves other tier estimates alone — the
    # serving hold-back must not learn that 1024 dispatches are free
    # from repeated caller-formed sheds
    assert cm.expected_s() == pytest.approx(overall / 2)
    assert cm.expected_s(256) == pytest.approx(t256 / 2)
    assert cm.expected_s(1024) == pytest.approx(0.030)
    # with an overall sample present, decay halves ONLY that channel
    cm3 = CostModel()
    cm3.observe(0.004)
    cm3.observe(0.020, tier=1024)
    cm3.decay()
    assert cm3.expected_s() == pytest.approx(0.002)
    assert cm3.expected_s(1024) == pytest.approx(0.020)
    # floor applies to every readout
    cm2 = CostModel(floor_s=0.5)
    cm2.observe(0.001, tier=256)
    assert cm2.expected_s(256) == 0.5


def test_serving_handle_shares_admission_cost_model(store_world):
    """The batcher's hold-back and the client's deadline shed read the
    SAME CostModel object — no duplicated EWMA (the satellite's whole
    point)."""
    c, _oracle = store_world
    h = c.with_serving()
    try:
        assert h.batcher._cost is c._admission.cost
        # a serving dispatch feeds the per-tier estimate the deadline
        # shed reads through expected_cost_s
        ctx = background()
        h.check(ctx, rel.must_from_triple("repo:r0", "read", "user:u0"))
        assert c._admission.cost.has_samples()
        assert c._admission.expected_cost_s(256) > 0.0
    finally:
        h.close()


def test_serving_handle_enforces_overlap_required():
    """with_overlap_required applies to the serving surface too — the
    handle must not drop the guard the client was configured with."""
    from gochugaru_tpu.client import with_overlap_required
    from gochugaru_tpu.consistency import with_overlap_key
    from gochugaru_tpu.utils.errors import OverlapKeyMissingError

    c = new_tpu_evaluator(with_overlap_required())
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """)
    txn = rel.Txn()
    txn.touch(rel.must_from_triple("doc:d", "reader", "user:u"))
    c.write(ctx, txn)
    r = rel.must_from_triple("doc:d", "read", "user:u")
    with c.with_serving() as h:
        with pytest.raises(OverlapKeyMissingError):
            h.check(ctx, r)
        with pytest.raises(OverlapKeyMissingError):
            h.submit(ctx, r)
        assert h.check(with_overlap_key(ctx, "k"), r) == [True]


def test_tiered_costs_do_not_inflate_tierless_estimate():
    """Whole-batch serving costs (tier-tagged) must not inflate the
    tier-less estimate the deadline shed reads — a hot serving pool of
    expensive 4096-tier batches would otherwise spuriously shed every
    small deadline-bearing direct check."""
    cm = CostModel()
    cm.observe(0.001)              # small caller-formed dispatches
    for _ in range(20):
        cm.observe(0.050, tier=4096)   # hot serving traffic
    assert cm.expected_s() == pytest.approx(0.001)
    assert cm.expected_s(4096) == pytest.approx(0.050)
    # serve-only process (no tier-less samples): the shed estimate is
    # the CHEAPEST tier, not the priciest
    cm2 = CostModel()
    cm2.observe(0.050, tier=4096)
    cm2.observe(0.002, tier=256)
    assert cm2.expected_s() == pytest.approx(0.002)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_dispatcher_death_settles_futures_and_closes():
    """A BaseException escaping dispatch (the emergency path) must not
    strand its batch's futures or leave later submitters hanging: the
    in-flight batch rejects in the settle backstop and the batcher
    closes itself."""
    reg = metrics.Metrics()
    calls = []

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        calls.append(1)
        raise SystemExit("simulated dispatcher death")

    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), registry=reg,
        config=ServeConfig(hold_max_s=0.0005),
        dispatch_cols=dispatch_cols,
    )
    one = np.zeros(1, np.int32)
    fut = b.submit_columns("c", one, one, one)
    with pytest.raises(UnavailableError):
        fut.result(timeout=10.0)
    # the emergency close lands asynchronously; new submissions are
    # refused once it does
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline:
        try:
            f2 = b.submit_columns("c", one, one, one)
        except UnavailableError:
            break  # closed
        try:
            f2.result(timeout=10.0)
        except UnavailableError:
            pass
        time.sleep(0.01)
    else:
        pytest.fail("batcher never closed after dispatcher death")
    assert reg.counter("serve.thread_crashes") >= 1


def test_bulk_item_error_slices_per_submission():
    """A batch-relative BulkCheckItemError from the evaluation slices
    back onto submissions: earlier ones resolve from the partial
    results, the failing one gets a SUBMISSION-relative error with only
    its own verdicts, later ones reject retriable (their envelopes
    re-submit) — no cross-submitter verdict leakage, no out-of-range
    index."""
    from gochugaru_tpu.utils.errors import BulkCheckItemError

    def dispatch_rels(rels, latency, span):
        # item 6 (0-based) fails; verdicts 0..5 were accumulated
        raise BulkCheckItemError(6, [True] * 6, ValueError("bad caveat"))

    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), start=False,
        registry=metrics.Metrics(), dispatch_rels=dispatch_rels,
    )
    r = rel.must_from_triple("doc:d", "read", "user:u")
    fa = b.submit_rels("A", [r] * 4)   # fully evaluated
    fb = b.submit_rels("B", [r] * 4)   # fails at its item 2
    fc = b.submit_rels("C", [r] * 4)   # never evaluated
    batch = b.form_batch()
    assert batch.total == 12
    b.dispatch_batch(batch)
    assert fa.result() == [True] * 4
    with pytest.raises(BulkCheckItemError) as ei:
        fb.result()
    assert ei.value.index == 2            # submission-relative
    assert ei.value.results == [True] * 2  # B's own verdicts only
    with pytest.raises(UnavailableError):
        fc.result()                        # retriable → re-submits
    b.close()


def test_bulk_item_error_cols_ndarray_slicing():
    """The columnar evaluation raises BulkCheckItemError with ndarray
    partial results (client._evaluate_columns per-item isolation) — the
    batcher's slicing handles that shape identically to the rels path's
    list results."""
    from gochugaru_tpu.utils.errors import BulkCheckItemError

    def dispatch_cols(q_res, q_perm, q_subj, latency, span):
        raise BulkCheckItemError(
            6, np.ones(6, bool), ValueError("bad item")
        )

    b = MicroBatcher(
        tiers=(256,), cost=CostModel(), start=False,
        registry=metrics.Metrics(), dispatch_cols=dispatch_cols,
    )
    four = np.zeros(4, np.int32)
    fa = b.submit_columns("A", four, four, four)
    fb = b.submit_columns("B", four, four, four)
    fc = b.submit_columns("C", four, four, four)
    b.dispatch_batch(b.form_batch())
    assert np.asarray(fa.result()).tolist() == [True] * 4
    with pytest.raises(BulkCheckItemError) as ei:
        fb.result()
    assert ei.value.index == 2
    assert np.asarray(ei.value.results).tolist() == [True, True]
    with pytest.raises(UnavailableError):
        fc.result()
    b.close()


def test_batchpath_costs_tagged_not_tierless():
    """Breaker-open (batch-path) dispatch costs tag the cost model with
    the batch's target cap, never the tier-less channel the deadline
    shed reads."""
    from gochugaru_tpu.utils.admission import CircuitBreaker

    cm = CostModel()
    br = CircuitBreaker(1, 1000.0, registry=metrics.Metrics())
    br.record_failure()  # trips OPEN
    b = MicroBatcher(
        tiers=(256,), cost=cm, breaker=br, start=False,
        registry=metrics.Metrics(),
        config=ServeConfig(batch_path_max=512),
        dispatch_cols=lambda q_res, q_perm, q_subj, latency, span:
            np.zeros(q_res.shape[0], bool),
    )
    one = np.zeros(8, np.int32)
    fut = b.submit_columns("c", one, one, one)
    batch = b.form_batch()
    assert batch.tier is None and batch.target == 512  # re-tiered
    b.dispatch_batch(batch)
    fut.result()
    assert cm.expected_s() == 0.0 or not cm.has_samples() or (
        cm.expected_s(512) > 0.0
    )
    # the tier-less overall channel stayed empty; the cost landed on
    # the 512 cap key
    assert cm.expected_s(512) > 0.0
    assert cm.expected_s(99999) == cm.expected_s(512)  # min-tier fallback
    b.close()


def test_metrics_histogram_and_prometheus_render():
    """The fixed-bucket histogram counts correctly (inclusive uppers,
    +Inf overflow) and renders as a Prometheus histogram with
    cumulative le buckets."""
    from gochugaru_tpu.utils.telemetry import render_prometheus

    reg = metrics.Metrics()
    for v in (1, 64, 64, 200, 256, 5000):
        reg.observe_hist("serve.batch_fill", v, (64, 256, 1024))
    hs = reg.hist_snapshot()
    buckets, counts, n, total, exemplars = hs["serve.batch_fill"]
    assert buckets == (64.0, 256.0, 1024.0)
    assert counts == [3, 2, 0, 1]  # le64: 1,64,64; le256: 200,256; +Inf: 5000
    assert n == 6 and total == pytest.approx(5585.0)
    assert exemplars == [None] * 4  # no trace ids recorded yet
    # exemplar recording: the LAST trace id per bucket, value + stamp
    reg.observe_hist("serve.request_latency", 40, (64, 256), trace_id="t-a")
    reg.observe_hist("serve.request_latency", 41, (64, 256), trace_id="t-b")
    ex = reg.hist_snapshot()["serve.request_latency"][4]
    assert ex[0][0] == "t-b" and ex[0][1] == 41.0 and ex[0][2] > 0
    assert ex[1:] == [None] * 2
    snap = reg.snapshot()
    assert snap["serve.batch_fill.le_64"] == 3
    assert snap["serve.batch_fill.le_256"] == 5  # cumulative
    assert snap["serve.batch_fill.count"] == 6
    text = render_prometheus(reg)
    assert "# TYPE gochugaru_serve_batch_fill histogram" in text
    assert 'gochugaru_serve_batch_fill_bucket{le="256"} 5' in text
    assert 'gochugaru_serve_batch_fill_bucket{le="+Inf"} 6' in text
    assert "gochugaru_serve_batch_fill_count 6" in text


# ---------------------------------------------------------------------------
# stage accounting: a served request is covered end to end
# ---------------------------------------------------------------------------

def test_serve_stages_account_for_caller_latency(store_world):
    """Over 200 requests from 8 threads the four serve intervals — queue
    wait, formed-batch wait, dispatch, wake — add up to the latency the
    callers measured themselves, within 10 % in total: nothing of a
    served request is outside every stage."""
    c, _oracle = store_world
    ctx = background()
    m = metrics.default
    parts = ("serve.queue_wait_s", "serve.formed_wait_s", "serve.dispatch_s",
             "serve.wake_s")
    measured = []
    with c.with_serving() as h:
        warm = _rand_checks(np.random.default_rng(99), 6)
        h.check(ctx, *warm)  # the tier's first dispatch may compile
        snap0 = m.snapshot()

        def worker(w):
            lr = np.random.default_rng(100 + w)
            mine = []
            for _ in range(25):
                qs = _rand_checks(lr, 6)
                t0 = time.perf_counter()
                h.check(ctx, *qs, client_id=w)
                mine.append(time.perf_counter() - t0)
            measured.extend(mine)

        ts = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap1 = m.snapshot()
    delta = lambda k: snap1.get(k, 0) - snap0.get(k, 0)
    assert len(measured) == 200
    assert delta("serve.queue_wait_s.count") == 200  # one a submission
    assert delta("serve.wake_s.count") == 200
    batches = delta("serve.dispatch_s.count")
    assert delta("serve.formed_wait_s.count") == batches  # one a batch
    # per-submission intervals sum as they are; a per-batch interval is
    # paid by every submission of its batch, so it enters at its mean
    staged = sum(delta(f"{p}.total_s") / delta(f"{p}.count") for p in parts)
    mean = sum(measured) / len(measured)
    assert abs(staged - mean) <= 0.10 * mean, (staged, mean, batches)
    # the dispatcher's own leaves ran once a batch
    for p in ("serve.concat_s", "serve.settle_s", "serve.form_s"):
        assert delta(f"{p}.count") == batches, p
    assert delta("serve.idle_s.count") >= 1
