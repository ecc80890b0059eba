"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, before their first
compile; library modules and tests never do. ``JAX_COMPILATION_CACHE_DIR``
wins where it is set. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of the cache key, so it is
never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it. Leaves a set ``JAX_COMPILATION_CACHE_DIR`` alone. JAX reads the
    variable when it is imported, so where it already is, the config is
    updated as well."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(DEFAULT_DIR))
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
