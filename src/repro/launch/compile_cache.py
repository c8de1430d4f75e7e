"""Where the entry points keep JAX's persistent compilation cache.

A cold process on the chip spends much of its start-up compiling; the
cache lets the next process (the second engine of a serve A/B, the next
``chip_smoke.py`` run on the same disk) load those executables instead.
The cache directory is part of each entry's key, so it is a fixed path:
never one built from a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for this process; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself, and nothing is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
