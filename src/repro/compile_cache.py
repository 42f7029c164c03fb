"""JAX's persistent compilation cache, pointed at one fixed directory.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``)
call :func:`enable_compile_cache` before their first compile; importing the
library never does. The directory is part of the cache's key, so it never
moves between runs:

* ``$JAX_COMPILATION_CACHE_DIR`` when that variable is set (JAX reads it
  itself; nothing else is configured);
* otherwise ``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout fallback: ``<repo>/.jax_cache`` (this file is
#: ``<repo>/src/repro/compile_cache.py``).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> Path:
    """``$JAX_COMPILATION_CACHE_DIR`` when set and non-empty, else the
    in-checkout default."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
