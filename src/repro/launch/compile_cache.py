"""JAX's persistent compilation cache, for the entry points that run on
the chip (``launch/serve.py`` and ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here overrides it. Otherwise the cache goes to
``.jax_cache`` at the root of the checkout (git-ignored): a fixed path,
since the path is part of what a later process must find again. Called
from ``main()`` functions only, never at import — the tests run without
a cache.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
