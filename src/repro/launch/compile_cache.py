"""JAX's persistent compilation cache, for the entry points that run a chip.

Compiling a whole fused program for the TPU takes seconds to minutes; a
process that finds its executables in the persistent cache skips that.  The
cache key includes the directory, so the directory must not move between
runs: ``enable_compile_cache`` points JAX at

* ``$JAX_COMPILATION_CACHE_DIR`` where it is set — that directory and no
  other;
* otherwise ``<checkout>/.jax_cache``, one fixed path (listed in
  ``.gitignore``).

Entry points call it before their first compile (``chip_smoke.py``,
``repro.launch.serve``); importing the library never does.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py → <checkout>
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
