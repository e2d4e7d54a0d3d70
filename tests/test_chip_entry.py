"""The chip entry points off the chip: ``chip_smoke.py`` refuses without a
TPU, and the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to a fixed directory inside the repository."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import cache

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = {"JAX_PLATFORMS": "cpu", "PATH": os.environ.get("PATH", "")}
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_without_tpu():
    r = _run_smoke(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_env_dir_wins(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

