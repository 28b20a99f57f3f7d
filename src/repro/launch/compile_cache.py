"""JAX's persistent compilation cache for the entry points.

``serve.py``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
``configure_compile_cache()`` before their first compile, so a second run
of the same programs loads them instead of compiling again.  Tests do not
call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache: one fixed path inside the checkout (listed in .gitignore);
#: the path is part of the cache key, so it must not move between runs
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, is read by JAX itself and nothing else is set; otherwise the
    cache lives at ``CHECKOUT_CACHE``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
