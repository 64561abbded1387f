"""JAX's persistent compilation cache for the entry points.

Called from ``chip_smoke.py`` and the ``train``/``serve`` launchers'
``main``, never at import of ``repro``: a library import must not change
process-wide JAX settings.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, so that a later process in
    the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
