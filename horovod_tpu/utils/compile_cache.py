"""Where compiled programs are kept between runs.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
``tools/*_bench.py`` scripts, and ``tpurun`` for its children): when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing is set
in code; otherwise the cache lives in ``<checkout>/.jax_cache``, resolved from
this package's location.  The path is part of every cache key, so it is never
a temporary name, a pid or a time — a directory that moves never hits.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache``, the same from any working directory."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def cache_dir() -> str:
    """The directory in use: the variable's when set, else the default."""
    return os.environ.get(ENV) or default_dir()


def enable() -> str:
    """Switch the persistent cache on before the first compile; returns
    the directory in use."""
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", default_dir())
    return cache_dir()
