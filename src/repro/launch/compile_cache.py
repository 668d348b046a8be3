"""Where the entry points keep JAX's persistent compilation cache.

A full-width serving program takes tens of seconds to compile on the chip;
the persistent cache turns a restart's compiles into disk reads.  The
cache key includes the directory, so it lives at a fixed path: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself), or
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

__all__ = ["DEFAULT_CACHE_DIR", "use_compilation_cache"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set
    this changes nothing (JAX already uses that directory); otherwise it
    points JAX at ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
