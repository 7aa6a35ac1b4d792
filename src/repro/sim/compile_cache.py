"""JAX's persistent compilation cache for the repo's entry points.

`repro.sim.sweep.main` and ``chip_smoke.py`` call `enable_compile_cache`
before their first compile.  The cache key includes the directory, so
the default is a fixed path inside the checkout, never one built from
a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/src/repro/sim/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else at `DEFAULT_DIR`; returns the directory."""
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
