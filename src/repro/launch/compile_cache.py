"""JAX's persistent compilation cache, placeable from outside.

A full-depth training step takes tens of seconds to compile for a TPU, and
every fresh process pays it again unless the compiled program is cached on
disk.  The cache directory is part of what a later process must find, so it
is never derived from a temporary name, a process id or the time:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself and it stands;
  nothing here sets another directory;
* unset — the cache lives at ``<checkout>/.jax_cache`` (git-ignored).

Every entry point (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``, ``chipbench/``) calls :func:`enable_compile_cache`
before its first JAX computation.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
