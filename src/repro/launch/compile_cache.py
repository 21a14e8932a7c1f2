"""Where JAX's persistent compilation cache lives for the entry points.

A published-width fed step takes tens of seconds to compile, and a chip
machine starts every call with nothing compiled, so the entry points
(``fed_train``, ``sim``, ``benchmarks.run``, ``chip_smoke.py``) keep
compiled programs on disk.  The cache key includes the directory, so the
fallback is one fixed path per checkout: never a temporary name, a
process id or a time.  Called from ``main()`` only — importing a module
must not change JAX's configuration.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it at
    import and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
