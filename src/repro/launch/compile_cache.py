"""Where the entry points keep JAX's persistent compilation cache.

A cold start compiles every step program of a full-width model, which
takes minutes on a TPU; the persistent cache lets the next run of the
same programs skip that.  The cache only helps at a path that does not
move between runs, so it is either the operator's choice
(``$JAX_COMPILATION_CACHE_DIR``) or one fixed directory of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Call it first thing in an entry point's ``main``, never at import.
    With ``$JAX_COMPILATION_CACHE_DIR`` set JAX already reads it, and
    nothing is set here; otherwise the cache goes to ``<checkout>/
    .jax_cache``.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
