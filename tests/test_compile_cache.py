"""``use_compile_cache``: JAX's persistent compilation cache is placed from
outside (``JAX_COMPILATION_CACHE_DIR``) or else at a fixed path in the
checkout, and only when an entry point asks."""
import os

import jax

from repro.common.compile_cache import use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_importing_entry_points_places_no_cache():
    import importlib
    before = jax.config.jax_compilation_cache_dir
    for mod in ("repro.launch.train", "benchmarks.run"):
        importlib.import_module(mod)
    assert jax.config.jax_compilation_cache_dir == before
