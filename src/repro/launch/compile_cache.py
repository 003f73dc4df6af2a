"""Where JAX keeps compiled programs between runs of an entry point.

A full-width warm-up compiles dozens of step programs; JAX's persistent
compilation cache lets the next run of the same checkout load them instead.
Entry points call :func:`use_compile_cache` from ``main()`` — never a library
module at import, so tests and other callers keep whatever cache they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (listed in ``.gitignore``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing else is set here. Otherwise the cache lives at
    :data:`DEFAULT_DIR`, one fixed path inside the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
