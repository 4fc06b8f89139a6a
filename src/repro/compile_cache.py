"""Where JAX's persistent compilation cache lives.

One helper for every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
the examples).  The cache path is part of what a later process looks up,
so it is fixed: the directory named by ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it, otherwise ``.jax_cache/`` in the checkout —
never a temporary directory or a name built from a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package was imported from (``src/repro`` -> root)
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps its cache
    there and nothing else is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
