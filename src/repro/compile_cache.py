"""JAX persistent compilation cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the examples,
``repro.launch.train``) calls ``enable_compile_cache()`` once, before its
first compile. Importing the package never turns the cache on.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and this sets nothing; otherwise the cache lives at ``.jax_cache/`` in the
root of the checkout — a fixed path, because the path is part of what a
later run must find again (never a temp, pid or time-based name).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
