"""Where JAX keeps its persistent compilation cache.

A compile cache only hits when its directory stays put: the path is
part of what a later process looks up.  So the directory is either the
one ``JAX_COMPILATION_CACHE_DIR`` names — JAX reads that variable
itself, and this module then sets nothing — or a fixed directory in
the checkout, ``<repo>/.jax_cache`` (git-ignored).  It is never derived
from a temporary name, a process id or the time.

Entry points that compile the model call ``enable_compile_cache()``
once, before their first compile.  Tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX already uses that
    directory, so nothing is configured here.  Otherwise the cache goes
    to the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
