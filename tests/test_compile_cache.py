"""The persistent compilation cache lives at one fixed directory."""
from pathlib import Path

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache


def test_cache_dir_env_wins_else_repo_root(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
