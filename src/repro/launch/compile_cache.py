"""Persistent compile cache for the program's entry points.

Compiling a published-width train or serve step takes tens of seconds, and
every fresh process pays it again unless JAX's persistent cache is on.  The
entry points (``launch.train``, ``launch.serve``, ``benchmarks.run`` and
``chip_smoke.py``) call ``enable_compile_cache()`` once, before they
compile anything; importing ``repro`` never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a fixed path lets the next process of this
    checkout find the entries again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
