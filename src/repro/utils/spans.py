"""Host spans: named intervals of the host's work around the device's.

``span(name, **args)`` enters a ``jax.profiler.TraceAnnotation``, so that a
profiler trace holds the span beside the device's operations (``args``
become the trace event's arguments; the spans of one chunk of rounds share
``round`` and ``rounds``). With no profiler running a span costs about
3 us of a TPU v5e host's CPU. It also works as a decorator. Where JAX's
profiler has not been imported, no trace can be running, so a span enters no
annotation: this module imports no JAX, and the device-free layers that
carry spans (the partitioner) stay free of it.

``record_to(sink)`` attaches ``sink`` for the calling thread: each span that
thread closes while it is attached also hands ``(name, t0, t1)``, on
``time.perf_counter``, to ``sink.append``. A span records nothing where no
sink is attached, and spans of other threads never reach it, so the spans
in one sink nest.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time

_attached = threading.local()


@contextlib.contextmanager
def span(name: str, **args):
    sink = getattr(_attached, "sink", None)
    profiler = sys.modules.get("jax.profiler")
    with (profiler.TraceAnnotation(name, **args) if profiler is not None
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sink is not None:
                sink.append((name, t0, time.perf_counter()))


@contextlib.contextmanager
def record_to(sink):
    """Attach ``sink`` to the calling thread's spans until the block ends
    (the sink attached before, if any, is attached again after)."""
    before = getattr(_attached, "sink", None)
    _attached.sink = sink
    try:
        yield sink
    finally:
        _attached.sink = before
