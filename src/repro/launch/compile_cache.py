"""JAX's persistent compilation cache, kept at one fixed place.

A cache hit needs the same cache directory on every run, so a directory
named after a temp dir, a process id or the time never hits.
``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
alone; otherwise the cache lives in the checkout (``.jax_cache/``,
git-ignored).  Entry points call ``enable`` before their first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
