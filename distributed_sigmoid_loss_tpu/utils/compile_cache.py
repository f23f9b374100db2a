"""Where the persistent XLA compilation cache lives and what keys it — the one
place that says.

A cold B/16 train step costs minutes of compile; every entry point (``cli.main``,
``bench.py``, ``chip_smoke.py``, ``__graft_entry__.py``, the test bootstrap) calls
:func:`configure_compile_cache` so repeated runs hit disk instead. The directory
must be placeable from outside (a chip machine may mount its own), hence the rule:

- ``JAX_COMPILATION_CACHE_DIR`` set (even to ``""``, which disables the cache):
  the directory is left alone — jax reads the variable itself.
- otherwise: ``<checkout>/.jax_cache``, a fixed git-ignored path, so a second
  process started from the same checkout finds the first one's programs.

Either way the key includes the program's metadata
(``jax_compilation_cache_include_metadata_in_key``). By default jax hashes the
module after ``strip-debuginfo`` (jax/_src/cache_key.py), and the names a
profile reads — the ``jax.named_scope`` paths of train/train_step.py — live in
exactly that debug info: two programs that differ only by a scope share a key,
and the cache hands the second one the first one's executable, whose profile
carries the old names or none. With the metadata in the key a stale name is a
miss instead. The price: the key also holds source files and lines, so an edit
that moves a line of any function on the traced path costs that program one
cold compile; a checkout that does not change keeps hitting. File names are
written relative to the checkout (``jax_hlo_source_file_canonicalization_regex``
strips its path), so two checkouts that share a cache directory (a parent and a
change side by side, an unpacked archive) share every program whose traced
source is the same in both.
"""

from __future__ import annotations

import os
import re

__all__ = ["CACHE_ENV", "default_cache_dir", "configure_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def default_cache_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in use ("" = disabled)."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", "^" + re.escape(_CHECKOUT + os.sep)
    )
    if CACHE_ENV in os.environ:
        return os.environ[CACHE_ENV]
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
