"""Host spans and marks on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs (``jax.profiler.start_trace``, or the benchmark's
``--trace 1``) it becomes a host event in the trace's ``.xplane.pb``, on
the same clock as the device's operations, with ``meta`` as the event's
stats; while none runs it costs well under a microsecond.  ``mark`` is a
zero-length span, an event to count.

Names begin with the program's layer (``session/``, ``batcher/``,
``bmpr/``).  Spans of one stream carry its ``sid`` and ``chunk``; the span
that caused another is the one that encloses it on the host thread.  The
profiler's trace is the only export: nothing is kept in memory.

Device work is scoped with ``jax.named_scope`` where it is traced (the
``ardit/...`` scopes of the model step): that is compile-time metadata,
read from each XLA operation's ``op_name`` in a device trace.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A host span; use as ``with span("batcher/prep"):``."""
    return TraceAnnotation(name, **meta)


def mark(name: str, **meta) -> None:
    """A zero-length span: one counted event with its stats."""
    with TraceAnnotation(name, **meta):
        pass
