"""JAX's persistent compilation cache, inside the checkout."""
from __future__ import annotations

import os


def configure(root: str) -> str:
    """Keep every compiled program in ``<root>/.jax_cache``, a fixed path
    in the checkout, also for code that reads ``JAX_COMPILATION_CACHE_DIR``.
    Small programs are kept too, so a second run compiles nothing."""
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache
