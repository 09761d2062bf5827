"""JAX persistent compilation cache for the entry points.

Each entry point that owns the device (chip_smoke.py, bench.py's
measured child, cli/kube_scheduler) calls enable() once, before its
first compile. Nothing calls it at import time, so the test suite never
touches the cache.

Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at a fixed path inside
the checkout (.jax_cache, listed in .gitignore): the directory is part
of what makes a later run find an entry, so it is never derived from a
temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
