"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here overrides it.  Otherwise the cache goes to
``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path, so a
later run from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
