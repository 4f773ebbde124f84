"""The benchmark's own spans around its calls into each layer.

Each span is written twice: into a list on ``time.monotonic()`` (for
``tick_ms``, ``input_wait_share``: time in the call over the window) and,
through ``jax.profiler.TraceAnnotation``, into the profiler's own trace,
so that a traced run can say what the host was doing in a device gap.
Spans inside the program are the ``tracing`` issue's.
"""
from __future__ import annotations

import contextlib
import time

import jax

from .xplane import SPAN_PREFIX


@contextlib.contextmanager
def span(sink, name):
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        try:
            yield
        finally:
            sink.append((name, t0, time.monotonic()))


def seconds_in(sink, name, t0, t1):
    """Seconds of [t0, t1] spent inside spans called ``name``, and how
    many of them started there."""
    total, count = 0.0, 0
    for n, a, b in sink:
        if n != name or b <= t0 or a >= t1:
            continue
        total += min(b, t1) - max(a, t0)
        count += a >= t0
    return total, count
