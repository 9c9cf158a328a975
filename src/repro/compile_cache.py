"""Where JAX keeps its persistent compilation cache for this checkout.

``enable_compile_cache()`` is called once at start-up by the entry points
that run on a chip (``chip_smoke.py``, ``repro.launch.search``,
``repro.launch.search_serve``):

  - with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable
    itself, and nothing is set here;
  - otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path,
    so that a later process of the same checkout finds what an earlier
    one compiled (the path is part of the cache key; a temp-, pid- or
    time-derived directory would never hit).
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
