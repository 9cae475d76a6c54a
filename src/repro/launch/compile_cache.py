"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, since the path is part of what a later run must find again. The entry
points (``kv_serve``, ``train``, ``serve``, ``chip_smoke.py``) call
:func:`enable` before they compile anything.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
