"""JAX's persistent compilation cache for the repository's entry points.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py`` and the
``repro.launch`` launchers) calls :func:`enable_compile_cache` before it
compiles anything, so a second run with the same programs loads them
instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in ``.gitignore``).  It is a
#: fixed path because the directory is part of each entry's key: a cache
#: that moves is never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is set here; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
