"""Host spans of the serving tick, in the profiler's own trace.

``span(name, **meta)`` names a phase of a scheduler tick
(``serve.tick``, ``serve.readback``, ``serve.advance``, ...) as a
``jax.profiler.TraceAnnotation``.  Inside a profiler session the
profiler keeps it in memory and writes it, at ``stop_trace``, to the
host plane of the same trace that holds the device's operations, so a
span and the device work it waited on share one clock.  Outside a
session an annotation records nothing and costs about a microsecond.
"""
from __future__ import annotations

import jax

PREFIX = "serve."


def span(name: str, **meta):
    """A ``serve.<name>`` host span; ``meta`` (ints or strings) rides
    along as the span's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
