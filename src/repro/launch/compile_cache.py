"""JAX's persistent compilation cache: one place decides where it lives.

Entry points call `enable_compile_cache()` before their first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache (listed in .gitignore).  A fixed path: the cache
# key includes it, so a directory that moved between runs would never hit.
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and it is the
    only directory used: nothing is set here.  Otherwise the cache is
    DEFAULT_DIR, inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
