"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is honored as is: JAX reads it
itself and this module sets no directory of its own.  Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout, a fixed path that
``.gitignore`` lists, so every later run in the same checkout finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
