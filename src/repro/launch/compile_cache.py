"""JAX's persistent compilation cache for the launch binaries.

A cold start on the chip spends most of its time compiling, so the
binaries keep compiled programs on disk.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself; only when that is unset does
:func:`enable` choose a directory, and then always the same one inside
the checkout (``.jax_cache/``, git-ignored): the path is part of what a
cache entry is found by, so a directory that moves never hits.
"""
from __future__ import annotations

import pathlib

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call from a binary's ``main``, never at import: a library that sets
    the cache as a side effect of being imported would override its
    host program's choice."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
