"""Where JAX keeps its persistent compilation cache.

Entry points (``launch.train``, ``launch.serve``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start-up; nothing calls it at import.
The cache's directory is part of what makes an entry found again, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself), else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
