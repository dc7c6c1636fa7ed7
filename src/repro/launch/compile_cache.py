"""JAX's persistent compilation cache, for the repository's entry points.

``enable_compile_cache()`` is called once at start-up by each entry point
(``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``), never on import and never by the tests.

- If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  function changes nothing.
- Otherwise the cache goes to ``.jax_cache/`` at the root of the checkout,
  found from this file's location. The path is part of each entry's key, so
  it never depends on a temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
