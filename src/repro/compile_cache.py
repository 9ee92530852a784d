"""JAX's persistent compilation cache, placed for this checkout.

Called by the entry points (``chip_smoke.py``, ``python -m repro.service``,
``benchmarks/run.py``), never at library import.  A cold run on a TPU
compiles every program of the keyed, chunked and event-time paths, which
takes minutes; with the cache, a second process on the same machine reads
them back.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  set here.
* Otherwise: ``<checkout>/.jax_cache`` (git-ignored).  The path is fixed —
  it is part of what a later run has to find again.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
