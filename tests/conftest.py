"""Test bootstrap: force JAX onto CPU with 8 virtual devices so mesh/sharding
logic is exercised without TPU hardware — the moral equivalent of the
reference's `spicedb serve-testing` in-memory server (SURVEY.md §4).

The platform is overridden, not setdefault: on a machine with a TPU the
suite would otherwise run on one chip with per-shape XLA compiles.  Set
GOCHUGARU_TEST_TPU=1 to deliberately run the suite against the real chip."""

import os

if os.environ.get("GOCHUGARU_TEST_TPU") != "1":
    from gochugaru_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(8)

# persistent XLA compile cache: identical kernels (same schema shape
# buckets) hit disk instead of recompiling across test runs
from gochugaru_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

# GOCHUGARU_FLAT_ALIGNED=1 runs the whole suite under the bucket-ALIGNED
# table layout (engine/hash.py build_aligned — the TPU-default layout,
# otherwise off on the CPU suite).  Scoped to the test harness on
# purpose: production code paths must not read layout toggles from the
# environment.
_env_aligned = os.environ.get("GOCHUGARU_FLAT_ALIGNED")
if _env_aligned is not None:
    from gochugaru_tpu.engine.plan import EngineConfig

    _orig_for_schema = EngineConfig.for_schema

    def _for_schema_aligned(compiled, **overrides):
        overrides.setdefault("flat_aligned", _env_aligned == "1")
        return _orig_for_schema(compiled, **overrides)

    EngineConfig.for_schema = staticmethod(_for_schema_aligned)


# Fault-injection hygiene: no test may leak an armed injection site into
# the next (utils/faults.py is a process-global registry by design).
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running harnesses excluded from tier-1 (-m 'not slow')",
    )


@pytest.fixture(autouse=True)
def _reset_faults():
    from gochugaru_tpu.utils import faults

    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _reset_recorder():
    """Flight-recorder hygiene (utils/trace.py): the recorder is a
    process-global by design (the trigger bus must be reachable from
    anomaly sites without plumbing); no test may leak an installed one
    into the next — a leaked recorder would make every unsampled request
    allocate flight-only spans and break the zero-alloc contract
    tests."""
    yield
    from gochugaru_tpu.utils import slo, trace

    trace.install_recorder(None)
    slo.install_engine(None)  # closes a leaked process-global engine


# Multi-host capability probe: some container jaxlib builds cannot run
# multiprocess collectives on the CPU backend at all ("Multiprocess
# computations aren't implemented on the CPU backend") — an ENVIRONMENT
# limitation, not a code defect.  Probe it once (two 1-device processes,
# jax.distributed init + one cross-process broadcast) and skip the
# multi-host tests with the detected reason instead of carrying known-red
# failures in tier-1.
_MULTIHOST_PROBE = []  # memo: [None] = supported, [reason str] = not

_PROBE_SRC = """
import os
import numpy as np
import jax
jax.distributed.initialize(
    os.environ["GOCHUGARU_PROBE_COORD"], 2,
    int(os.environ["GOCHUGARU_PROBE_PID"]),
)
from jax.experimental import multihost_utils
multihost_utils.broadcast_one_to_all(np.ones(1, np.int32))
print("MULTIHOST-PROBE-OK")
"""


def _multihost_unavailable_reason():
    """None when the environment can run multi-process CPU collectives,
    else a one-line reason string (cached per session)."""
    if _MULTIHOST_PROBE:
        return _MULTIHOST_PROBE[0]
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            GOCHUGARU_PROBE_COORD=coord,
            GOCHUGARU_PROBE_PID=str(pid),
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PROBE_SRC], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    reason = None
    for pr in procs:
        try:
            out, _ = pr.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            pr.kill()
            out, _ = pr.communicate()
            reason = reason or "probe timed out (collective hung)"
            continue
        if pr.returncode != 0 or "MULTIHOST-PROBE-OK" not in (out or ""):
            tail = [
                ln for ln in (out or "").splitlines()
                if "Error" in ln or "error" in ln
            ]
            reason = reason or (
                tail[-1].strip()[:160] if tail else "probe process failed"
            )
    _MULTIHOST_PROBE.append(reason)
    return reason


@pytest.fixture(autouse=True)
def _skip_unsupported_multihost(request):
    if request.module.__name__ == "test_multihost":
        reason = _multihost_unavailable_reason()
        if reason is not None:
            pytest.skip(f"multi-host env unavailable: {reason}")
    yield
