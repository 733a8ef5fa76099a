"""JAX's persistent compilation cache for the entry points.

``launch.serve``, ``launch.train`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, so a later run on
the same machine reads compiled programs back instead of recompiling them
(a full-width serving engine jits a dozen programs).  Importing this
module changes nothing.

The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise in ``<checkout>/.jax_cache`` (gitignored).  The path is fixed on
purpose: it is part of what lets a later run find the entries.
"""

from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or the
    in-checkout default, cache every program regardless of how long it took
    to compile, and return the directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # the cache binds its directory on first use; drop a binding made
        # earlier in this process so the new directory takes effect
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
