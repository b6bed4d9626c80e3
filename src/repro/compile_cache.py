"""Where JAX's persistent compilation cache lives.

Entry points (`chip_smoke.py`, `benchmarks.run`, `repro.serve.http`)
call `enable_compile_cache()` once at start-up, before anything compiles;
importing this module changes nothing.  The cache's location is part of
what makes an entry hit, so it never comes from a temporary directory,
a pid or the clock:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself, and this
    helper sets no other directory;
  * unset: the cache goes to `<checkout>/.jax_cache` (listed in
    `.gitignore`).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
