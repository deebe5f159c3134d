"""Where JAX keeps its persistent compilation cache for this checkout.

A chip run compiles every program of a grid cold; the persistent cache
lets later processes of the same checkout load them instead.  The cache
directory is part of each entry's key, so it must not move between runs:
either the operator's ``JAX_COMPILATION_CACHE_DIR`` or a fixed directory
inside the checkout -- never a temporary name, a pid or a time.

By default JAX strips debug info (locations, hence ``named_scope``
names) from the key, so a program that differs from a cached one only in
its scopes would load the other's executable, whose ``op_name``
metadata -- what a device trace's operations are mapped back to -- is
stale.  The key here includes that metadata: a checkout's first run of a
program compiles it, and later runs of the same checkout load it.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here.  Otherwise the cache is
    ``<checkout>/.jax_cache``.  Either way the key includes the
    program's metadata.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
