"""JAX's persistent compilation cache for the command-line entry points.

A cold process on the chip spends much of its start-up compiling the
engines; with the cache on, a second run of the same program finds the
executables on disk. The cache key includes the directory, so the
directory must not move between runs: it is either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or
the fixed ``.jax_cache/`` at the root of the checkout.

Entry points call :func:`enable_compile_cache` once, before their first
compile. Library code never calls it: importing ``repro`` changes no
cache setting.
"""
from __future__ import annotations

import os

_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no other
    directory is configured; otherwise the checkout's own is."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CHECKOUT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
