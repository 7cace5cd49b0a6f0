"""JAX's persistent compilation cache, configured in one place.

Entry points (``launch.train``, ``launch.serve``,
``examples/tune_gains.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory
  itself and nothing is set here;
* otherwise the cache lives in ``.jax_cache/`` at the root of the
  checkout (listed in ``.gitignore``).  The path is fixed because it is
  part of what a later process must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
