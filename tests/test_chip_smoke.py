"""Process start-up and the entry scripts (ISSUE 21): ``chip_smoke.py``
refuses to run off the chip, its explicit CPU rehearsal passes end to end,
the compile cache is placed from outside, every backend-keyed knob
resolves in one place, and the bench entry points neither probe nor fall
back and fail when their child fails."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        cwd=ROOT, env=env, timeout=600,
    )


def test_bare_smoke_without_a_tpu_fails_and_prints_no_result():
    r = _run_smoke()
    assert r.returncode != 0
    assert r.stdout == ""
    assert "not a TPU" in r.stderr


def test_cpu_rehearsal_passes_and_says_cpu_on_every_line():
    r = _run_smoke("--rehearse-cpu", "--partitioned")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()]
    # the last line is the driver's contract, these keys and no others;
    # the line before it is the report
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    assert lines[-1] == {"ok": True, "device": device}
    assert all(ln["platform"] == "cpu" for ln in lines[:-1])
    last = lines[-2]
    assert last["event"] == "report" and last["device"] == device
    assert last["native_available"] is True
    assert set(last["resolved"]) == {
        "flat_aligned", "flat_packed", "flat_pipeline_batch",
    }
    labels = [s["label"] for s in last["sections"]]
    assert labels == ["one-device", "mesh-1x4", "mesh-1x4-partitioned"]
    for sec in last["sections"]:
        c = sec["counters"]
        assert c["checks.oracle"] == 0 and c["retry.retries"] == 0
        assert c["checks.fallback_overflow"] == 0
        assert c["checks.fallback_conditional"] == 0
        assert c["breaker.latency_rerouted"] == 0
        assert sec["bulk"]["oracle_samples"] >= 1000
        assert all(t["oracle_samples"] >= 200 and
                   t["warm_compile_requests"] == 0
                   for t in sec["tiers"].values())
        assert sec["served"]["oracle_samples"] >= 200
        assert sec["write"]["read_back"] and sec["write"]["old_revision_denies"]
    # who serves the lookups: fused SpMM on one device, the owner-routed
    # device frontier on sharded tables, the host walker on the
    # partitioned feed (it declines the reverse index)
    served_by = [
        tuple(int(s["counters"][f"lookups.{k}"])
              for k in ("fused", "frontier", "walker"))
        for s in last["sections"]
    ]
    assert served_by == [(2, 2, 0), (0, 2, 0), (0, 0, 2)]
    for sec in last["sections"][1:]:
        per_dev = sec["per_device_bytes"]
        assert max(per_dev.values()) * 2 <= sum(per_dev.values())


# ---------------------------------------------------------------------------
# the compile cache is placed from outside
# ---------------------------------------------------------------------------


@pytest.fixture()
def config_updates(monkeypatch):
    import jax

    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_compile_cache_leaves_an_outside_directory_alone(
    monkeypatch, config_updates
):
    from gochugaru_tpu.utils import platform

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert platform.configure_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_defaults_inside_the_checkout(
    monkeypatch, config_updates
):
    from gochugaru_tpu.utils import platform

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert platform.configure_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want


def test_no_hard_coded_cache_directory_is_left():
    tree = ["gochugaru_tpu", "benchmarks", "scripts", "tests", "bench.py",
            "chip_smoke.py", "__graft_entry__.py"]
    hits = subprocess.run(
        ["grep", "-rlE", "/tmp/gochugaru" + "_xla_cache|jax_compilation_cache_dir",
         "--include=*.py", "--include=*.sh", *tree],
        capture_output=True, text=True, cwd=ROOT,
    ).stdout.split()
    assert sorted(hits) == [
        "gochugaru_tpu/utils/platform.py", "tests/test_chip_smoke.py",
    ]


# ---------------------------------------------------------------------------
# backend-keyed knobs resolve in one place
# ---------------------------------------------------------------------------


def test_engine_config_resolves_backend_keyed_choices(monkeypatch):
    import jax

    from gochugaru_tpu.engine.plan import EngineConfig

    assert EngineConfig().resolved() == {
        "flat_aligned": False, "flat_packed": True,
        "flat_pipeline_batch": 0,
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert EngineConfig().resolved() == {
        "flat_aligned": True, "flat_packed": True,
        "flat_pipeline_batch": 32_768,
    }
    forced = EngineConfig(flat_aligned=False, flat_pipeline_batch=0)
    assert forced.resolved()["flat_aligned"] is False
    assert forced.resolved()["flat_pipeline_batch"] == 0


# ---------------------------------------------------------------------------
# bench entry points: no probe, no fallback, a failed child fails the run
# ---------------------------------------------------------------------------


def test_bench_exits_nonzero_when_its_child_fails(monkeypatch, capsys):
    import bench

    for gone in ("_probe_backend", "_child_body_cpu", "PROBE_CACHE_PATH"):
        assert not hasattr(bench, gone)
    row = {"metric": bench.HEADLINE_METRIC, "value": 1.0, "platform": "cpu"}
    monkeypatch.setattr(bench, "_run_child", lambda t: ({row["metric"]: row},
                                                        None))
    assert bench.main() == 0
    assert json.loads(capsys.readouterr().out) == row
    # a child that died after printing rows: rows relayed, run failed
    monkeypatch.setattr(bench, "_run_child",
                        lambda t: ({row["metric"]: row}, "child rc=1"))
    assert bench.main() == 1
    assert json.loads(capsys.readouterr().out) == row
    monkeypatch.setattr(bench, "_run_child", lambda t: (None, "child rc=1"))
    assert bench.main() == 1
    assert capsys.readouterr().out == ""


def test_run_all_exits_nonzero_when_a_child_fails(monkeypatch, tmp_path):
    from benchmarks import run_all

    assert not hasattr(run_all, "probe_backend")
    seen_env = []

    def fake(name, cmd, timeout_s, env):
        seen_env.append(env)
        if name.startswith("1 "):
            return [], [], "rc=1: boom"
        return ([{"metric": "m", "value": 1.0, "platform": "cpu"}], [], None)

    monkeypatch.setattr(run_all, "run_config", fake)
    out = tmp_path / "B.md"
    monkeypatch.setattr(sys, "argv", ["run_all.py", "--quick", "--out",
                                      str(out)])
    assert run_all.main() == 1
    assert "platform **cpu**" in out.read_text()
    assert all("GOCHUGARU_FORCE_CPU" not in e
               and "GOCHUGARU_BACKEND_PROBED" not in e for e in seen_env)
    monkeypatch.setattr(
        run_all, "run_config",
        lambda *a: ([{"metric": "m", "value": 1.0, "platform": "cpu"}],
                    [], None),
    )
    assert run_all.main() == 0
