"""Host spans on the profiler's clock.

``span("stage1.launch", tiles=1024, padded=1024)`` is a
``jax.profiler.TraceAnnotation`` named ``er.stage1.launch`` whose
arguments are the counts of the work it brackets. Spans land in the
profiler's own trace beside the device ops, so host phases and device
time share one clock; spans opened inside another on the same thread
nest under it. With no profiler running, entering and leaving a span
is a flag check.

A count known only once the work is done is added inside the span with
``set_metadata``::

    with span("stage1.decode") as sp:
        rows = np.nonzero(mask)
        sp.set_metadata(survivors=rows[0].size)
"""
from __future__ import annotations

import jax

__all__ = ["PREFIX", "span"]

PREFIX = "er."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """The host span ``er.<name>``, with ``counts`` as its arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
