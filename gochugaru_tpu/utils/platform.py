"""Process start-up: which backend JAX runs on and where it keeps its
persistent compile cache.

One definition here so the test conftest, the multichip dryruns, the
benchmarks and ``chip_smoke.py`` cannot drift.  The device is whatever
JAX finds: a TPU where there is one (one process per chip — a parent
that has touched JAX holds the chip), the CPU under ``JAX_PLATFORMS=cpu``.
Nothing here probes a backend or falls back from one to another.
"""

from __future__ import annotations

import os
import re

#: the compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (the path is part of JAX's cache key, so a
#: directory that moves never hits)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set in code; otherwise the cache lives at
    ``<repo>/.jax_cache``.  Every entry script calls this and nothing
    else sets a cache directory."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return env_dir or REPO_CACHE_DIR


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Pin JAX to the CPU backend with ``n_devices`` virtual devices.

    ``JAX_PLATFORMS=cpu`` alone selects the CPU; this exists because the
    virtual device count (``--xla_force_host_platform_device_count``, the
    CPU stand-in for a mesh) must be in ``XLA_FLAGS`` before the backend
    starts, replacing any pre-set count.  Call it before any JAX work."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
    # a caller may have imported jax already (the backend itself starts
    # lazily, so XLA_FLAGS above still takes effect)
    import jax

    jax.config.update("jax_platforms", "cpu")
