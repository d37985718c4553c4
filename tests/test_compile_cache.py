"""The entry points' persistent compilation cache: where it lives."""
from pathlib import Path

import jax

from repro.launch.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                        compile_cache_dir,
                                        enable_compile_cache)

REPO = Path(__file__).resolve().parents[1]


def test_environment_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir == prev


def test_default_is_one_fixed_ignored_checkout_path(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first, second = compile_cache_dir(), compile_cache_dir()
    assert first == second == str(REPO / ".jax_cache") == str(DEFAULT_DIR)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
