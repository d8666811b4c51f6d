"""Persistent XLA compilation cache shared by the launchers.

``serve``, ``train`` and ``chip_smoke.py`` call ``enable_compile_cache``
before their first compile, so a second process on the same checkout
loads its programs instead of compiling them. Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and nothing is set
here. Otherwise the cache lives at one fixed directory inside the
checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): the path is
part of what the cache is keyed on, so it never depends on a temporary
name, a PID or the time. The CPU backend gets no in-checkout cache: its
ahead-of-time loader logs an error for every entry it reads back.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; returns its directory
    (None on the CPU backend when the environment names none)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
