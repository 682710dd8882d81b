"""``chip_smoke.py`` must never pass on a host without a TPU, and the
compile-cache helper it calls must respect ``JAX_COMPILATION_CACHE_DIR``."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout, proc.stdout


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch):
    from repro.utils.compile_cache import compile_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_compile_cache_env_is_left_to_jax(monkeypatch):
    from repro.utils.compile_cache import compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache_dir() is None
