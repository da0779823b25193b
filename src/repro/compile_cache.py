"""JAX's persistent compilation cache for the entry points that run on
the chip (``chip_smoke.py``, ``benchmarks/run.py``).

Called from an entry point's ``main``, never at import: tests and
library users keep JAX's defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout's root (src/repro/compile_cache.py -> two levels up).
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no other directory is set; otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache`` (a fixed path, because the path
    is part of the cache key)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
