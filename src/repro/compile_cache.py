"""JAX's persistent compilation cache for the repo's entry points.

Called by the command-line entry points (``chip_smoke.py``,
``python -m repro.launch.serve``, ``benchmarks/run.py``), never at
import time, so library users and the tests keep JAX's default (off).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed in-checkout cache directory.  A cache entry is found again
#: only under the same path, so it is never built from a temporary
#: name, a pid or the time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and nothing else is set here.  Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
