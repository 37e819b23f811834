"""Where JAX's persistent compilation cache lives: one rule for every entry
point that turns it on (bench.py, chip_smoke.py, the scripts).

The test suite keeps the cache off (tests/conftest.py).
"""

from __future__ import annotations

import os
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns the directory this
    call set, or None when `JAX_COMPILATION_CACHE_DIR` is set — JAX reads
    that variable itself, so whoever set it places the cache.

    Otherwise the cache sits at a fixed path in the checkout (the path is
    part of the cache key, so a moving directory never hits):
    `.jax_cache`, or `.jax_cache_cpu` on the CPU backend, whose entries
    carry the compiling host's CPU features."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    name = ".jax_cache_cpu" if jax.default_backend() == "cpu" else ".jax_cache"
    path = os.path.join(_REPO, name)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
