"""The trace reduction on a small trace recorded on one v5e (PR 24:
``run.py --workload rbac10m.bulk --sizes rehearsal --seconds 1 --trace 1``,
8 requests of 8,192 checks from 4 callers), and its interval arithmetic."""

import os

import numpy as np
import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_rbac_rehearsal.xplane.pb")


def test_union_of_overlapping_nested_and_disjoint_intervals():
    start = np.array([10.0, 0.0, 2.0, 3.0, 20.0, 21.0])
    end = np.array([12.0, 5.0, 4.0, 8.0, 25.0, 22.0])
    s, e = trace_reduce.union_intervals(start, end)
    assert s.tolist() == [0.0, 10.0, 20.0] and e.tolist() == [8.0, 12.0, 25.0]
    none = np.zeros(0)
    assert trace_reduce.union_intervals(none, none)[0].shape == (0,)


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(RECORDED)


def test_busy_union_on_the_recorded_trace(summary):
    assert summary["devices"] == 1 and summary["device_events"] == 1312
    # 1,312 device operations, 22.15 ms in which one ran
    assert summary["busy_s"] == pytest.approx(0.022152081, rel=1e-6)
    # the operations' own seconds add up to no less than their union
    assert sum(s for _, s in summary["device_ops"]) <= 0.05
    assert len(summary["device_ops"]) == 10
    assert all(len(n) <= trace_reduce.NAME_CHARS for n, _ in summary["device_ops"])


def test_idle_share_and_gap_list_on_the_recorded_trace(summary):
    """The window the harness's request spans cover is 1.358 s; busy and
    the named gaps fill it, and nearly all of the idle time is host Python
    that no span covers."""
    gaps = dict(summary["idle_gaps"])
    window_s = 1.3578968730000014  # the run's own "window" line
    idle_share = 1.0 - summary["busy_s"] / window_s
    assert idle_share == pytest.approx(0.9837, abs=1e-3)
    assert sum(gaps.values()) + summary["busy_s"] == pytest.approx(window_s, rel=0.01)
    assert max(gaps, key=gaps.get) == trace_reduce.UNTRACED
    assert trace_reduce.OWN_SPAN not in gaps
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.016, abs=0.002)


def test_a_trace_with_no_device_operation_reads_nothing(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    summary = trace_reduce.reduce_trace(trace_reduce.find_xplane(str(tmp_path)))
    assert summary["busy_s"] is None and summary["idle_gaps"] == []
