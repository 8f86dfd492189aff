"""Process set-up shared by every entry point: the persistent compile cache
and the device-count check.

Every entry point (``repro.launch.simulate``, ``repro.launch.campaign``,
``repro.testing.conformance``, the ``benchmarks.pdes_perf`` child and
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile.  Tests do not: they keep JAX's default (no persistent cache).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``src/repro/launch/runtime.py`` → three levels up).
REPO_ROOT = Path(__file__).resolve().parents[3]


def default_cache_dir() -> Path:
    """The cache's fixed home inside the checkout (listed in .gitignore).

    Fixed on purpose: the directory is part of what a cached entry is found
    by, so a per-run name would never hit."""
    return REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and no other directory is
    set (JAX reads the variable itself); otherwise the cache goes to
    :func:`default_cache_dir`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def too_few_devices(have: int, need: int) -> str:
    """The error text for a run that needs more devices than are visible.

    It names the platform seen; only on ``cpu`` does it suggest the flag
    that splits the host into virtual devices."""
    import jax

    platform = jax.default_backend()
    msg = f"{have} {platform} device(s) visible, need {need}"
    if platform == "cpu":
        msg += (f" — set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{need}")
    return msg
