"""JAX's persistent compilation cache, set up in one place.

Entry points (``python -m repro``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile; nothing calls
it at import time, so importing the package never changes JAX's
configuration. The directory is part of the cache's key, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
