"""Host spans in the profiler's trace.

Every layer boundary of the engine opens one span, named ``repro.<layer>.
<phase>``: the sweep service (``repro.service.*``), the executor
(``repro.stream.*``) and the wire (``repro.wire.*``).  Keyword ids (a
ticket, a step) ride along as the span's metadata, so a trace can tie
the spans of one request together.  Spans live on the profiler's own
clock, next to the device's ops, and cost about a microsecond when no
profiler session is running.

:func:`timed` is the only clock of a boundary whose duration also goes
into ``StreamResult.stats``: it opens the span and adds the same
boundary's ``perf_counter`` duration to ``acc[key]``.
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation


def span(name: str, **ids) -> TraceAnnotation:
    """A host span named ``name`` (``repro.<layer>.<phase>``) with
    ``ids`` as its metadata."""
    return TraceAnnotation(name, **ids)


@contextlib.contextmanager
def timed(acc: dict, key: str, name: str, **ids):
    """Open span ``name`` and add its wall duration to ``acc[key]``.

    Yields the ``perf_counter`` reading at the span's start, for
    stamps taken against it."""
    with TraceAnnotation(name, **ids):
        t0 = time.perf_counter()
        try:
            yield t0
        finally:
            acc[key] += time.perf_counter() - t0
