"""Where JAX keeps its persistent compilation cache for this repository."""
from __future__ import annotations

import os

import jax


def use_compile_cache(root: str) -> str:
    """Return the cache directory in use, pointing JAX at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one (JAX reads that
    variable itself, and then nothing is set here).

    The path is fixed, never per run: a directory named by a temporary
    name, a PID or the time would start empty every time. Call before the
    first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
