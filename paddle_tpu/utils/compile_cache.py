"""Where the persistent XLA compile cache lives.

A chip-tool call starts with no compiled code, and a ragged tick plus a
train step at real widths is minutes of compile. The cache directory is
placed from OUTSIDE the program: where ``JAX_COMPILATION_CACHE_DIR`` is
set JAX reads it itself and nothing here sets another; otherwise the
cache goes to ``<checkout>/.jax_cache`` — a fixed path, because the
path is part of the cache key (a ``tempfile``/pid/time directory never
hits). ``chip_smoke.py`` calls :func:`configure` before its first
compile.
"""
from __future__ import annotations

import os

__all__ = ["configure"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Point JAX's persistent compile cache at its directory and
    return that directory. Touches no device."""
    env = os.environ.get(_ENV)
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
