"""JAX's persistent compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and nothing
else is configured. Otherwise the cache is ``<checkout>/.jax_cache``
(gitignored): one fixed path, never derived from a temporary name, a PID or
the time, so a later run of the same checkout finds it again. Entry points
(``chip_smoke.py``, the benchmark scripts) call :func:`enable` before their
first compile; importing the package never touches the cache.
"""
from __future__ import annotations

import os

import jax


def enable(root: str) -> str:
    """Turn the persistent cache on for the checkout at ``root``; returns
    the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
