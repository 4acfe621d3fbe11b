"""Host spans on the profiler's clock (DESIGN.md §18).

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session collects, it lands on the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device planes, with each of ``ids`` as a stat of the
event; otherwise it costs one TraceMe check and nothing is recorded. Pass
ids as plain values (``rid=req.rid``), never as strings built for the
span, so an idle span builds nothing.

Counters stay in ``ServingEngine.stats``; compiles are counted by
``repro.compile_cache.compile_counts``.
"""

from __future__ import annotations

import jax


def span(name: str, **ids):
    """A host span named ``name`` carrying ``ids`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name, **ids)
