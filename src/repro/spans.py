"""Program spans on the profiler's clock.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: while a profiler trace is active it records a host event
in the same ``.xplane.pb`` as the device's operations, with ``counts`` as
the event's stats (bytes moved, elements updated, items computed).  Counts
known only inside the span go in with the annotation's ``set_metadata``.
With no trace active a span costs about a microsecond.

The span tree (``docs/ARCHITECTURE.md``, "Observability") is read by
``chipbench/program_spans.py``.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
