"""The chip smoke script's contract off the chip, and the compile cache
helper the entry points share."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _smoke(*args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                           *args], env=env, capture_output=True, text=True,
                          timeout=300)


def test_smoke_refuses_a_cpu_backend_and_names_it(tmp_path):
    r = _smoke(tmp_path=tmp_path)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_rehearsal_passes_every_check_but_never_reports_ok(tmp_path):
    r = _smoke("--rehearse", tmp_path=tmp_path)
    assert "every check passed" in r.stderr, r.stdout + r.stderr
    assert r.returncode != 0
    assert "FAILED" not in r.stdout
    for name in ("phase A vertical == horizontal",
                 "phase B plan bytes == measured bytes",
                 "phase B layer output on cpu"):
        assert f"check {name}: ok" in r.stdout
    assert '"ok"' not in r.stdout
    assert not (ROOT / ".smoke_ssd").exists()


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        d = enable_compile_cache()
        assert d == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
        assert enable_compile_cache() == d
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
