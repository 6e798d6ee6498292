"""JAX's persistent compilation cache for the entry points.

A cache is only found again where it was written, so its place is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, and nothing is set here), otherwise ``.jax_cache/`` at the
root of the checkout (listed in ``.gitignore``). Entry points call
:func:`enable_compile_cache` once, before their first compile; library code
and tests never do.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
