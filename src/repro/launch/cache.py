"""JAX's persistent compilation cache, set up in one place.

Entry points (``python -m repro``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile; nothing calls
it at import time, so importing the package never changes JAX's
configuration. The directory is part of the cache's key, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<checkout>/.jax_cache``.

The key includes the programs' metadata. An executable loaded from the
cache keeps the metadata of the program that wrote it, and the train
step's named scopes (``repro/core/scopes.py``) live in metadata alone:
without it in the key, a profile can show another version's scopes, or
none. Source paths in the metadata are taken relative to the checkout,
so a checkout that moves still finds its entries.
"""
from __future__ import annotations

import os
import re

import jax

CHECKOUT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                         "..", "..", ".."))
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(CHECKOUT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
