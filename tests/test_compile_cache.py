"""Where the persistent compilation cache lands (``launch/compile_cache``)."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]

# compile something and list what the cache directory then holds
CHILD = """
import os, sys
sys.path.insert(0, {src!r})
from repro.launch.compile_cache import use_compile_cache
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
d = use_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
print(d, len(os.listdir(d)))
"""


def _child(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    r = subprocess.run([sys.executable, "-c",
                        CHILD.format(src=str(REPO / "src"))],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    where, n = r.stdout.split()[-2:]
    return where, int(n)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    where, n = _child(tmp_path)
    assert where == str(tmp_path) and n > 0  # the cache landed there


def test_default_dir_is_fixed_in_checkout_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    where, n = _child(None)
    assert where == str(CHECKOUT_CACHE_DIR) and n > 0
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
