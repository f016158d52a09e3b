"""enable_compile_cache: JAX_COMPILATION_CACHE_DIR wins where it is set;
otherwise the cache goes to a fixed directory inside the checkout that git
ignores."""
import os
import subprocess
import sys

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_ONCE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def test_env_dir_is_used_and_written(tmp_path):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=os.path.join(ROOT, "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", COMPILE_ONCE],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())


def test_repo_dir_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert str(REPO_CACHE_DIR.parent) == ROOT
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
