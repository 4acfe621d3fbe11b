"""JAX persistent compilation cache and compile counter for the repo's
entry points.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the examples,
``repro.launch.train``) calls ``enable_compile_cache()`` once, before its
first compile. Importing the package never turns the cache on.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and this sets nothing; otherwise the cache lives at ``.jax_cache/`` in the
root of the checkout — a fixed path, because the path is part of what a
later run must find again (never a temp, pid or time-based name).

``compile_counts()`` says which programs compiled and for how long
(DESIGN.md §18): one ``jax.monitoring`` listener on the backend-compile
event, registered at its first call.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles: dict[str, list] | None = None


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def _on_duration(event: str, duration: float, *, fun_name: str = "?",
                 **_) -> None:
    if event == COMPILE_EVENT:
        entry = _compiles.setdefault(fun_name, [0, 0.0])
        entry[0] += 1
        entry[1] += duration


def compile_counts() -> dict[str, list]:
    """``{program name: [compiles, seconds]}`` of every JAX backend compile
    (persistent-cache loads included) since the first call; a copy. The
    name is the one JAX gives the program (``jit(_tick)``,
    ``jit(_prefill_chunk)``, ...)."""
    global _compiles
    if _compiles is None:
        import jax

        _compiles = {}
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return {k: list(v) for k, v in _compiles.items()}
