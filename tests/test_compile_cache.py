"""Where the persistent compilation cache goes.

``JAX_COMPILATION_CACHE_DIR`` wins and the helper then sets nothing;
without it the cache is ``.jax_cache`` at the root of the checkout.  The
helper's effect on this process is stubbed, so the suite itself keeps the
cache off; the one real compile runs in a child process.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch) -> list:
    calls: list = []
    monkeypatch.setattr(jax.config, "update", lambda *args: calls.append(args))
    return calls


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_the_checkout_cache(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_entries_land_in_the_env_dir(tmp_path):
    cache = tmp_path / "cache"
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(5.0)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache), JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), env.get("PYTHONPATH", "")])
    before = set(os.listdir(REPO / ".jax_cache")) if (REPO / ".jax_cache").is_dir() else set()
    subprocess.run([sys.executable, "-c", script], env=env, check=True, timeout=120)
    assert any(cache.iterdir())
    after = set(os.listdir(REPO / ".jax_cache")) if (REPO / ".jax_cache").is_dir() else set()
    assert after == before
