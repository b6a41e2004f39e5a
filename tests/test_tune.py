"""Workload-adaptive self-tuning (gochugaru_tpu/tune/): the offline
tuner's fixed-point and JSON round-trip contracts, the no-retrace and
parity invariants on tuned NON-pow2 tier ladders, and the online
controller's safety envelope — hysteresis, cooldown, bounded-move
convergence, the oscillation tripwire (flight-recorder incident), and
one-call revert to preset."""

from dataclasses import replace

import numpy as np
import pytest

from gochugaru_tpu import rel
from gochugaru_tpu.client import (
    new_tpu_evaluator,
    with_engine_config,
    with_host_only_evaluation,
    with_latency_mode,
    with_store,
)
from gochugaru_tpu.engine.device import DeviceEngine
from gochugaru_tpu.engine.latency import tier_for
from gochugaru_tpu.engine.plan import EngineConfig
from gochugaru_tpu.serve import ServeConfig
from gochugaru_tpu.tune import (
    OnlineController,
    TuneDiff,
    TuneTarget,
    apply_diff,
    collect_snapshot,
    propose,
)
from gochugaru_tpu.utils import metrics, perf, trace
from gochugaru_tpu.utils.context import background

from tests.test_latency_path import EPOCH, build_rbac_world, _random_queries

#: a ladder the offline tuner could emit: nothing pow2-aligned
TUNED_TIERS = (192, 576, 1344)


@pytest.fixture(autouse=True)
def _trace_hygiene():
    trace.disable()
    yield
    trace.disable()


def _synthetic_registry():
    """A registry describing a workload with an oversized 1024 tier,
    clock-bound flushes, and near-zero duplicate checks."""
    m = metrics.Metrics()
    for _ in range(40):
        m.observe_hist(
            "serve.occupancy.t1024", 120.0, (64, 128, 256, 512, 1024)
        )
        m.inc("serve.flush_maxhold")
    for _ in range(4):
        m.inc("serve.flush_full")
    m.inc("serve.checks", 1000)
    m.inc("serve.unique_checks", 990)
    return m


# ---------------------------------------------------------------------------
# offline tuner
# ---------------------------------------------------------------------------

def test_propose_fixed_point_and_json_roundtrip():
    """Applying a proposed diff and re-proposing against the SAME
    snapshot yields the empty diff (fixed point), and the diff survives
    JSON serialization bit-for-bit."""
    m = _synthetic_registry()
    eng = EngineConfig(latency_tiers=(256, 1024, 4096))
    srv = ServeConfig()
    snap = collect_snapshot(m, engine_config=eng, serve_config=srv)
    target = TuneTarget(engine=eng, serve=srv, cache_bytes=None)
    diff = propose(snap, target)
    assert diff, "the synthetic workload must produce proposals"
    knobs = {k.knob for k in diff.knobs}
    assert "latency_tiers" in knobs and "hold_max_s" in knobs
    for k in diff.knobs:
        assert k.evidence, f"{k.knob} proposal carries no evidence"
        assert k.predicted, f"{k.knob} proposal carries no prediction"
    tuned = apply_diff(target, diff)
    assert not propose(snap, tuned), "re-propose after apply must be empty"
    rt = TuneDiff.from_json(diff.to_json())
    assert rt == diff


def test_propose_quiet_on_thin_evidence():
    """An empty registry (no samples anywhere) proposes nothing — the
    tuner never moves a knob without measured evidence."""
    m = metrics.Metrics()
    snap = collect_snapshot(
        m, engine_config=EngineConfig(), serve_config=ServeConfig()
    )
    assert not propose(
        snap,
        TuneTarget(engine=EngineConfig(), serve=ServeConfig(),
                   cache_bytes=None),
    )


def test_tiers_rule_emits_non_pow2():
    """The ladder rule quantizes to 64-lane multiples, not powers of
    two: a tier whose p90 occupancy is 131 proposes 320 (p90 × 2.0
    burst headroom, rounded up to the 64-lane quantum)."""
    m = metrics.Metrics()
    for _ in range(32):
        m.observe_hist(
            "serve.occupancy.t1024", 131.0,
            (64, 131, 256, 512, 1024),
        )
    eng = EngineConfig(latency_tiers=(1024, 4096))
    snap = collect_snapshot(m, engine_config=eng, serve_config=ServeConfig())
    diff = propose(
        snap, TuneTarget(engine=eng, serve=ServeConfig(), cache_bytes=None)
    )
    kd = diff.get("latency_tiers")
    assert kd is not None
    assert 320 in kd.proposed, kd.proposed
    assert "131" in kd.evidence  # the measured number is in the story


def test_tiers_rule_inserts_below_shared_tier():
    """When the pad ledger shows non-batcher dispatches (direct calls,
    coalesced-answer sampling) still filling a rung the batcher leaves
    near-empty, the rule INSERTS the small tier instead of replacing —
    the ladder serves every dispatch path, not just the batcher's."""
    m = metrics.Metrics()
    for _ in range(32):
        m.observe_hist(
            "serve.occupancy.t1024", 20.0, (64, 131, 256, 512, 1024)
        )
    # 40 non-batcher dispatches at ~800 live lanes on the same tier
    for _ in range(40):
        perf.record_pad(1024, 800, m)
    # and the batcher's own 32 dispatches flow through the ledger too
    for _ in range(32):
        perf.record_pad(1024, 20, m)
    eng = EngineConfig(latency_tiers=(1024, 4096))
    snap = collect_snapshot(m, engine_config=eng, serve_config=ServeConfig())
    diff = propose(
        snap, TuneTarget(engine=eng, serve=ServeConfig(), cache_bytes=None)
    )
    kd = diff.get("latency_tiers")
    assert kd is not None
    assert kd.proposed == (128, 1024, 4096), kd.proposed
    assert "insert" in kd.evidence and "stays" in kd.evidence


# ---------------------------------------------------------------------------
# tuned non-pow2 ladders keep the latency-path contracts
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tuned_world():
    cs, snap, users, repos, slot = build_rbac_world()
    engine = DeviceEngine(
        cs, EngineConfig.for_schema(cs, latency_tiers=TUNED_TIERS)
    )
    dsnap = engine.prepare(snap)
    return engine, dsnap, snap, users, repos, slot


def test_nonpow2_tier_for_routing():
    assert tier_for(TUNED_TIERS, 1) == 192
    assert tier_for(TUNED_TIERS, 192) == 192
    assert tier_for(TUNED_TIERS, 193) == 576
    assert tier_for(TUNED_TIERS, 1344) == 1344
    assert tier_for(TUNED_TIERS, 1345) is None


def test_nonpow2_ladder_no_retrace_and_parity(tuned_world):
    """110 warm dispatches on a tuned (192, 576, 1344) ladder pay zero
    additional compiles and zero ``latency.retraces``, with answers
    identical to the throughput path."""
    engine, dsnap, snap, users, repos, slot = tuned_world
    lp = engine.latency_path(dsnap)
    q_res, q_perm, q_subj = _random_queries(users, repos, slot, 500, seed=23)
    retr0 = metrics.default.counter("latency.retraces")
    out = lp.dispatch_columns(q_res, q_perm, q_subj, now_us=EPOCH)
    assert out is not None
    assert lp.last_budget.tier == 576
    warm = lp.compile_count
    for i in range(110):
        d, p, o = lp.dispatch_columns(
            np.roll(q_res, i), q_perm, np.roll(q_subj, i), now_us=EPOCH
        )
        if i % 37 == 0:
            dd, pp, oo = engine.check_columns(
                dsnap, np.roll(q_res, i), q_perm, np.roll(q_subj, i),
                now_us=EPOCH,
            )
            assert (d == dd).all() and (p == pp).all() and (o == oo).all()
    assert lp.compile_count == warm, "non-pow2 ladder retraced"
    assert metrics.default.counter("latency.retraces") == retr0
    # a second tier of the tuned ladder also pins and stays warm
    lp.dispatch_columns(q_res[:100], q_perm[:100], q_subj[:100], now_us=EPOCH)
    assert lp.last_budget.tier == 192
    warm2 = lp.compile_count
    lp.dispatch_columns(q_res[:150], q_perm[:150], q_subj[:150], now_us=EPOCH)
    assert lp.compile_count == warm2


def test_nonpow2_ladder_pin_reuse_across_prepares(tuned_world):
    """Re-preparing the same geometry re-pins tuned-tier executables
    from the engine-wide cache with zero new compiles."""
    engine, dsnap, snap, users, repos, slot = tuned_world
    q_res, q_perm, q_subj = _random_queries(users, repos, slot, 150, seed=29)
    lp = engine.latency_path(dsnap)
    lp.dispatch_columns(q_res, q_perm, q_subj, now_us=EPOCH)
    dsnap2 = engine.prepare(snap)
    lp2 = engine.latency_path(dsnap2)
    out = lp2.dispatch_columns(q_res, q_perm, q_subj, now_us=EPOCH)
    assert out is not None
    assert lp2.compile_count == 0, "tuned-tier pins were not shared"


def test_serving_on_tuned_ladder_parity_and_occupancy():
    """A serving handle over a tuned non-pow2 ladder answers exactly
    like the host oracle, records per-tier occupancy histograms for the
    tuned tiers, and never retraces."""
    cfg = replace(EngineConfig(), latency_tiers=(48, 192, 576))
    c = new_tpu_evaluator(with_latency_mode(), with_engine_config(cfg))
    ctx = background()
    c.write_schema(ctx, """
    definition user {}
    definition doc { relation reader: user  permission read = reader }
    """)
    txn = rel.Txn()
    for i in range(40):
        txn.touch(rel.must_from_triple(f"doc:d{i}", "reader", f"user:u{i % 9}"))
    c.write(ctx, txn)
    oracle = new_tpu_evaluator(
        with_host_only_evaluation(), with_store(c.store)
    )
    from gochugaru_tpu import consistency
    cs = consistency.full()
    rng = np.random.default_rng(31)
    retr0 = metrics.default.counter("latency.retraces")
    with c.with_serving() as h:
        for _ in range(12):
            qs = [
                rel.must_from_triple(
                    f"doc:d{rng.integers(40)}", "read",
                    f"user:u{rng.integers(9)}",
                )
                for _ in range(6)
            ]
            assert list(h.check(ctx, *qs)) == list(oracle.check(ctx, cs, *qs))
    assert metrics.default.counter("latency.retraces") == retr0
    occ = [
        n for n in metrics.default.hist_snapshot()
        if n.startswith("serve.occupancy.t")
    ]
    assert "serve.occupancy.t48" in occ, occ


# ---------------------------------------------------------------------------
# online controller
# ---------------------------------------------------------------------------

class FakeBatcher:
    def __init__(self, **kw):
        self.config = ServeConfig(**kw)
        self._top = 4096
        self.applies = 0

    def apply_config(self, cfg):
        self.config = cfg
        self.applies += 1


class FakeVcache:
    def __init__(self, max_bytes):
        self.max_bytes = max_bytes

    def set_max_bytes(self, n):
        self.max_bytes = int(n)


def _deadline_window(m, n=10):
    for _ in range(n):
        m.inc("serve.flush_deadline")


def test_controller_hysteresis_dead_band():
    """Mid-band signals (no watermark crossed) move nothing, tick after
    tick — the controller holds still on ambiguous evidence."""
    m = metrics.Metrics()
    b = FakeBatcher()
    c = OnlineController(b, registry=m, cooldown_steps=0)
    for _ in range(5):
        # 50% maxhold / 20% deadline at 40% fill: inside every dead band
        for _ in range(5):
            m.inc("serve.flush_maxhold")
        for _ in range(2):
            m.inc("serve.flush_deadline")
        for _ in range(3):
            m.inc("serve.flush_full")
        for _ in range(4):
            m.observe_hist(
                "serve.occupancy.t1024", 410.0, (64, 128, 256, 512, 1024)
            )
        assert c.step() == 0
    assert b.applies == 0 and b.config == ServeConfig()


def test_controller_cooldown_blocks_next_move():
    m = metrics.Metrics()
    b = FakeBatcher()
    c = OnlineController(b, registry=m, cooldown_steps=1)
    _deadline_window(m)
    assert c.step() == 1 and b.config.hold_max_s == 0.001
    _deadline_window(m)
    assert c.step() == 0, "cooldown must block the very next tick"
    _deadline_window(m)
    assert c.step() == 1 and b.config.hold_max_s == 0.0005


def test_controller_converges_bounded_under_load_shift():
    """A sustained deadline-heavy shift walks hold down the ladder one
    bounded step per eligible tick, stops at the clamp, and never moves
    again under the same signal — convergence, not hunting."""
    m = metrics.Metrics()
    b = FakeBatcher()
    c = OnlineController(b, registry=m, cooldown_steps=0,
                         hold_bounds=(0.0005, 0.008))
    trajectory = [b.config.hold_max_s]
    for _ in range(8):
        _deadline_window(m)
        c.step()
        trajectory.append(b.config.hold_max_s)
    # monotone, bounded steps (each move is one ladder rung), clamped
    assert trajectory[0] == 0.002
    assert all(a >= z for a, z in zip(trajectory, trajectory[1:]))
    assert trajectory[-1] == 0.0005
    assert c.moves == 2  # 0.002 -> 0.001 -> 0.0005, then parked
    assert m.counter("tune.moves") == 2
    assert m.gauge("tune.hold_max_s") == 0.0005
    assert "hold_max_s" not in c._frozen


def test_controller_cache_knob_grow_shrink_clamped():
    m = metrics.Metrics()
    b = FakeBatcher()
    vc = FakeVcache(32 << 20)
    c = OnlineController(b, vcache=vc, registry=m, cooldown_steps=0,
                         cache_bounds=(16 << 20, 64 << 20))
    # hot + full + evicting -> grow x2
    m.inc("cache.hits", 50)
    m.inc("cache.misses", 50)
    m.inc("cache.evicted_revisions", 2)
    m.set_gauge("cache.bytes", float(int(0.9 * (32 << 20))))
    assert c.step() == 1 and vc.max_bytes == 64 << 20
    # still hot + full -> clamped at the ceiling, no further move
    m.inc("cache.hits", 50)
    m.inc("cache.misses", 50)
    m.inc("cache.evicted_revisions", 2)
    m.set_gauge("cache.bytes", float(int(0.9 * (64 << 20))))
    assert c.step() == 0
    # cold + idle -> shrink toward (and clamp at) the floor
    for _ in range(3):
        m.inc("cache.misses", 100)
        m.set_gauge("cache.bytes", 1024.0)
        c.step()
    assert vc.max_bytes == 16 << 20
    assert m.gauge("tune.vcache_bytes") == float(16 << 20)


def test_controller_dedup_off_only_on_measured_uniqueness():
    m = metrics.Metrics()
    b = FakeBatcher()
    c = OnlineController(b, registry=m, cooldown_steps=0)
    # heavy duplication: dedup stays on
    m.inc("serve.checks", 1000)
    m.inc("serve.unique_checks", 700)
    assert c.step() == 0 and b.config.dedup is True
    # near-total uniqueness: dedup turns off (and cannot turn back on)
    m.inc("serve.checks", 1000)
    m.inc("serve.unique_checks", 999)
    assert c.step() == 1 and b.config.dedup is False
    m.inc("serve.checks", 1000)  # no unique counting once off
    assert c.step() == 0 and b.config.dedup is False


def test_controller_oscillation_trips_incident_and_freezes():
    """Alternating raise/lower pressure flips the hold knob until the
    tripwire freezes it and captures a flight-recorder incident."""
    m = metrics.Metrics()
    rec = trace.install_recorder(
        trace.FlightRecorder(grace_s=0.0, cooldown_s=0.0)
    )
    b = FakeBatcher()
    c = OnlineController(b, registry=m, cooldown_steps=0, osc_flips=3)
    for i in range(12):
        if "hold_max_s" in c._frozen:
            break
        if i % 2 == 0:
            _deadline_window(m)  # pressure down
        else:  # pressure up: maxhold-bound at high fill
            for _ in range(10):
                m.inc("serve.flush_maxhold")
            for _ in range(5):
                m.observe_hist(
                    "serve.occupancy.t1024", 900.0,
                    (64, 128, 256, 512, 1024),
                )
        c.step()
    assert "hold_max_s" in c._frozen
    assert m.counter("tune.oscillations") >= 1
    assert m.gauge("tune.frozen_knobs") == 1.0
    assert any(
        i["trigger"] == "tune.oscillation" for i in rec.incident_index()
    )
    # frozen means frozen: the same pressure moves nothing
    held = b.config.hold_max_s
    _deadline_window(m)
    assert c.step() == 0 and b.config.hold_max_s == held


def test_controller_revert_restores_preset():
    m = metrics.Metrics()
    b = FakeBatcher()
    vc = FakeVcache(32 << 20)
    c = OnlineController(b, vcache=vc, registry=m, cooldown_steps=0)
    _deadline_window(m)
    c.step()
    m.inc("serve.checks", 1000)
    m.inc("serve.unique_checks", 999)
    c.step()
    for _ in range(3):
        m.inc("cache.misses", 100)
        m.set_gauge("cache.bytes", 1024.0)
        c.step()
    c._frozen.add("hold_max_s")
    assert b.config.hold_max_s != 0.002 or not b.config.dedup
    c.revert()
    assert b.config == ServeConfig()
    assert vc.max_bytes == 32 << 20
    assert c._frozen == set()
    assert m.counter("tune.reverts") == 1
    assert m.gauge("tune.hold_max_s") == 0.002
    assert m.gauge("tune.dedup") == 1.0
    # after revert the controller may move again (history cleared)
    _deadline_window(m)
    assert c.step() == 1
