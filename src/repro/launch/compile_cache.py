"""Where the launchers keep JAX's persistent compilation cache.

A full-width program takes seconds to minutes to compile, and a fresh
process compiles everything again unless the persistent cache already
holds it.  JAX keys the cache on its directory, so the directory must not
move between runs: it is a fixed path inside the checkout, never one made
from a temp name, a pid or the time.  Entry points call
:func:`enable_compile_cache` once, before their first compile; no library
module calls it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout that holds ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
