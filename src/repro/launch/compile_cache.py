"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py``) call
:func:`use_compile_cache` once at start-up, before the first compile;
nothing calls it at import or in tests. If ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this sets nothing. Otherwise the cache
goes to ``<checkout>/.jax_cache``: a fixed path, because the path is part
of what a later run must match to hit the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``src/repro/launch/compile_cache.py`` -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
