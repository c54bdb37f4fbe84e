"""Persistent JAX compilation cache: one placement rule for every entry point.

``chip_smoke.py``, the serve engine and both benches
(``benchmarks/search_bench.py``, ``benchmarks/serve_bench.py``) call
:func:`enable_compilation_cache` before their first compile, so a device
launch that took minutes to compile is loaded from disk on the next run.
The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and no
  directory is set in code, not even one a caller passes;
* otherwise the caller's ``path``, or else :data:`DEFAULT_DIR`
  (``<checkout>/.jax_cache``).  The default is fixed on purpose: the cache
  is found again only at the same path, so a directory derived from a
  temporary name, a pid or the time never hits.

Either way the min-compile-time and min-entry-size floors drop to 0/-1, so
small serve launches persist too.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_DIR", "ENV_VAR", "enable_compilation_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/serve/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache(path=None) -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    directory = os.environ.get(ENV_VAR)
    if not directory:
        directory = str(path or DEFAULT_DIR)
        Path(directory).mkdir(parents=True, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
            cc.reset_cache()  # drop a cache this process opened elsewhere
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory
