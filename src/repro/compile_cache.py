"""Where JAX keeps its persistent compilation cache.

A compile on the chip takes seconds to minutes, so the entry points
(``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``)
share one on-disk cache.  The cache's path is part of its key, so it
sits at one fixed place and is never built from a temp name, a pid or
the time.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored): this file is <checkout>/src/repro/
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
