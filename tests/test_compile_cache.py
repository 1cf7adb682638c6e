"""The entry points' persistent compilation cache: an environment
setting wins, otherwise one fixed, git-ignored directory in the
checkout."""
from pathlib import Path

import jax

from repro.compile_cache import CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_cache_dir_from_environment_or_fixed_in_checkout(monkeypatch):
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was[0]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
