"""JAX persistent compilation cache, at one fixed place per checkout.

``enable_compile_cache()`` is called once at start-up by every entry
point that compiles for a device (``chip_smoke.py``, ``benchmarks.run``,
``launch.serve``, ``launch.train``). Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here overrides it. Otherwise the
cache lives in ``<checkout>/.jax_cache``: a fixed path, because the
directory is part of what a later process looks up.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
