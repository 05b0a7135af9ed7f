"""Where JAX keeps its persistent compilation cache for this repo.

Entry points (``chip_smoke.py``, the ``benchmarks/`` scripts) call
:func:`enable_compile_cache` from their ``main`` before the first compile,
so a later process on the same machine loads the decision megakernel and
the jitted steps instead of compiling them again.  Never called at import.
"""
from __future__ import annotations

import os

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path at the root of the checkout (listed in ``.gitignore``).  The
#: directory is part of what makes an entry findable, so it never moves.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

#: Compiles at least this long are written to the cache (JAX's default,
#: 1 s, would drop most of this repo's kernels).
MIN_COMPILE_SECONDS = 0.1


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECONDS)
    return path


__all__ = ["CHECKOUT_CACHE_DIR", "MIN_COMPILE_SECONDS",
           "enable_compile_cache"]
