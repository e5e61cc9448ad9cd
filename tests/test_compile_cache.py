"""The entry points' persistent compilation cache directory."""
import pathlib

import jax

from repro.launch.compile_cache import enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX's own


def test_default_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == jax.config.jax_compilation_cache_dir
        assert pathlib.Path(first) == REPO / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
