"""JAX persistent compilation cache location.

Called from the ``main()`` of each entry point, never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
configured here. Otherwise the cache goes to ``<repo>/.jax_cache`` — a
fixed path (the path is part of the cache key, so a moving directory
never hits), listed in ``.gitignore``.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
