"""Where the persistent compile cache lives (utils/xla_cache.py): placed
from outside through JAX_COMPILATION_CACHE_DIR, else ``.jax_cache`` in
the checkout; turned on by programs where they start, never by library
code."""

import os

import jax
from jax.experimental.compilation_cache import compilation_cache

from swarmdb_tpu.utils import xla_cache


def test_jax_compilation_cache_dir_wins_and_is_left_alone(
        tmp_path, monkeypatch, restore_cache_config):
    """With the variable set, the directory is the variable's and
    ``jax.config`` is not touched: JAX read it at import (here it did
    not — the variable is set after — so the config still shows the
    session's value, proof that nothing updated it)."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert xla_cache.enable_compile_cache() == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "outside").exists()


def test_default_cache_dir_is_the_checkouts(monkeypatch, restore_cache_config):
    """Without the variable: ``<checkout>/.jax_cache``, derived from the
    package's location (the directory .gitignore lists)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert xla_cache.default_cache_dir() == want
    made = not os.path.isdir(want)
    try:
        assert xla_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert xla_cache.enable_compile_cache() == want      # idempotent
    finally:
        if made and os.path.isdir(want) and not os.listdir(want):
            os.rmdir(want)


def test_library_code_does_not_turn_the_cache_on(monkeypatch,
                                                 restore_cache_config):
    """Building an engine leaves the cache configuration as it found it:
    the suite's engines must not all write one directory from several
    workers (ROADMAP Design 9)."""
    from swarmdb_tpu.backend.service import build_backend_engine

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    build_backend_engine("tiny-debug", max_batch=2, max_seq=32)
    assert jax.config.jax_compilation_cache_dir is None
