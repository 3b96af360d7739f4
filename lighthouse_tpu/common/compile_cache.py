"""Where JAX's persistent compilation cache lives — one place.

Every entry point (bench.py, chip_smoke.py, __graft_entry__.py, the test
suite, tools/*) calls :func:`configure` before its first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets a directory. Otherwise the cache goes to ``<repo>/.jax_cache``:
a fixed path, because the path is part of what a later run must find
(``.gitignore`` lists it).
"""

from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")
ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory this process's compiles are cached in."""
    return os.environ.get(ENV) or DEFAULT_DIR


def configure(min_compile_secs: float = 2.0) -> str:
    """Turn the persistent cache on for compiles of at least
    ``min_compile_secs``; returns the directory in use."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return cache_dir()


def disable_persistent_cache() -> None:
    """Off for this process. For compiles against a described chip: such
    an entry is written but cannot be read back without the chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
