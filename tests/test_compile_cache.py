"""Where the entry points keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_alone_and_used(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a compile lands there and the
    checkout gets no cache of its own."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache\n"
        "print(compile_cache.enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8))).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir()), "no cache entry was written"
