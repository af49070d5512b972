"""Where JAX keeps its persistent compilation cache for this checkout.

Called once by the serving entry points (``launch/serve.py``,
``chip_smoke.py``) before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here, so the environment decides.
* unset: the cache goes to ``.jax_cache/`` at the checkout root — a fixed
  path, since the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its place; return the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
