"""Persistent XLA compilation cache wiring.

The big-model jit variants (decode chunk, per-rung prefills) each cost a
quarter of a minute to a minute and a half of XLA compile on first use.
JAX's persistent compilation cache stores the compiled executables on
disk keyed by HLO hash, so every process after the first (API server
restarts, each bench mode, a second ``chip_smoke.py``) deserializes
instead of recompiling.

The directory is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX itself took it at import and
nothing here touches it; otherwise the cache lives in ``.jax_cache``
beside the package (the checkout root — the path is part of the cache
key, so a directory that moves never hits). A program turns the cache
on once, where it starts (``api/server.py`` ``main``, ``chip_smoke.py``,
``bench.py``); library code never does, so the test suite's engines do
not all write one directory from several workers.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("swarmdb_tpu.xla_cache")

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, derived from the package's location."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def persistent_cache_programs(path: str) -> set:
    """Distinct compiled-program keys in a persistent-cache directory.

    The cache writes a ``<jit-name>-<hash>-cache`` / ``-atime`` file pair
    per program; this strips the suffix so one program counts once. Used
    by the precompile drift tests (compile-count == variant-count on a
    warm start) and handy for eyeballing what a warmup actually added:
    ``python -c "from swarmdb_tpu.utils.xla_cache import *; \
      print(sorted(persistent_cache_programs('.jax_cache')))"``."""
    try:
        names = os.listdir(path)
    except OSError:
        return set()
    return {n.rsplit("-", 1)[0] for n in names}


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return the directory in effect: ``JAX_COMPILATION_CACHE_DIR`` when
    set (left exactly as JAX read it), else :func:`default_cache_dir`.
    Idempotent; a directory that cannot be made is an error."""
    import jax

    path = os.environ.get(_ENV)
    if not path:
        path = default_cache_dir()
        if jax.config.jax_compilation_cache_dir != path:
            from jax.experimental.compilation_cache import compilation_cache

            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            # jax pins the cache object to the dir in effect at FIRST
            # use; a later config update alone is silently ignored
            compilation_cache.reset_cache()
    # cache everything that took meaningful compile time; the tiny helper
    # jits (health probe, token scatter) stay out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    logger.info("persistent XLA compilation cache at %s", path)
    return path
