"""Where the persistent compilation cache lands (``core.backend``)."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.core.backend import CHECKOUT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and a
    compile in a fresh process writes its entry there."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.core.backend import use_compile_cache\n"
            "use_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir())


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    """Without the variable the cache goes to one fixed, git-ignored
    directory of the checkout — no temp name, pid or timestamp."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(path).parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{Path(path).name}/" in ignored
