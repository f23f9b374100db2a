"""Where the persistent XLA compilation cache lives — the one place that says.

A cold B/16 train step costs minutes of compile; every entry point (``cli.main``,
``bench.py``, ``chip_smoke.py``, ``__graft_entry__.py``, the test bootstrap) calls
:func:`configure_compile_cache` so repeated runs hit disk instead. The directory
must be placeable from outside (a chip machine may mount its own), hence the rule:

- ``JAX_COMPILATION_CACHE_DIR`` set (even to ``""``, which disables the cache):
  touch nothing — jax reads the variable itself.
- otherwise: ``<checkout>/.jax_cache``, a fixed git-ignored path, so a second
  process started from the same checkout finds the first one's programs.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_ENV", "default_cache_dir", "configure_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in use ("" = disabled)."""
    if CACHE_ENV in os.environ:
        return os.environ[CACHE_ENV]
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
