"""Entry-point set-up: the placeable compile cache, the device-count message,
and chip_smoke.py refusing to report a result without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import runtime

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env_and_sets_no_other(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = runtime.default_cache_dir(), runtime.default_cache_dir()
    assert first == second
    assert first.parent == REPO
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.enable_compile_cache() == str(first)
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_too_few_devices_suggests_host_flag_only_on_cpu(monkeypatch):
    assert "xla_force_host_platform_device_count=4" in \
        runtime.too_few_devices(1, 4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    msg = runtime.too_few_devices(1, 4)
    assert "tpu" in msg and "XLA_FLAGS" not in msg


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert "platform=cpu" in r.stdout
