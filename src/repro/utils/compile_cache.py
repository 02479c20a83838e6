"""JAX's persistent compilation cache, at one fixed place per checkout.

A cache in a temporary or per-run directory is never found again, so the
default lives at one fixed place, ``<checkout>/.jax_cache`` (listed in
``.gitignore``).  Entry points call ``enable_compile_cache()``
from their ``main``; nothing calls it at import, so library users and the
test suite (whose ahead-of-time compiles for a described chip would write
entries that can never be read back) keep JAX's own default.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
