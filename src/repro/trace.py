"""Profiler spans at the layer boundaries of the served paths.

A span is a ``jax.profiler.TraceAnnotation``: it records only while a
profiler trace runs (``jax.profiler.start_trace``), on the trace's host
plane, one line per thread, on the clock of the device's operations.
With no trace running it costs about a microsecond, so the spans stay in
the code with no switch.  A span's name is its layer and step, the same
on every call (``store.probe``); per-call values go in as event stats
(``shard=3``), never in the name.  A span wraps work that already
happens: it adds no device sync and no copy.

The spans of one coalesced lookup batch carry the same ``batch`` stat.
The thread that executes the batch opens :func:`in_batch`; code that
hands part of the batch to another thread passes :func:`current_batch`
along and opens :func:`in_batch` there.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

__all__ = ["span", "in_batch", "current_batch"]

_BATCH: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_trace_batch", default=None
)


def span(name: str, **stats):
    """A profiler span named ``name``, with ``stats`` and the current
    batch id (if any) as its event stats."""
    from jax.profiler import TraceAnnotation

    batch = _BATCH.get()
    if batch is not None:
        stats.setdefault("batch", batch)
    return TraceAnnotation(name, **stats)


def current_batch() -> Optional[int]:
    return _BATCH.get()


@contextlib.contextmanager
def in_batch(batch: Optional[int]) -> Iterator[None]:
    """Spans opened inside carry ``batch`` (on this thread only)."""
    token = _BATCH.set(batch)
    try:
        yield
    finally:
        _BATCH.reset(token)
