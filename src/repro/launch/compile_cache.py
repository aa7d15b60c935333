"""Persistent JAX compilation cache for the command-line entry points.

Entry points call :func:`configure_compile_cache` at the start of their
``__main__`` path, before the first compile.  Importing the library never
touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout's root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at a fixed directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
    other directory is set here.  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, never one built from a temp
    name, a pid or the time, so a later run finds what an earlier one
    compiled.  Returns the directory in use.

    The cache key includes the programs' op metadata: an executable keeps
    the ``op_name`` metadata it was compiled with, and profiles read the
    ``sweep.*`` scopes from it, so a program must not load the executable
    of an equal program compiled from other source (another checkout's,
    or one without the scopes).
    """
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
