"""Host spans of the middleware's build phases.

A span is a :class:`jax.profiler.TraceAnnotation`, so it reaches a
profiler trace whenever one is running.  The build runs before a caller
opens its trace window, so the spans opened inside :func:`recording`
are also kept in memory, as ``(name, start_ns, end_ns, parent)`` tuples
on ``time.perf_counter_ns``'s clock, ``parent`` being the name of the
enclosing span (None at the top).  A span's self time is its duration
less its children's.  Outside a recording (a re-bind during a run) a
span goes to the trace only.
"""
from __future__ import annotations

import contextlib
import contextvars
import time

import jax

# (the recorded spans, the names of the open spans, innermost last)
_recording: contextvars.ContextVar = contextvars.ContextVar(
    "plug_build_spans", default=None)


@contextlib.contextmanager
def recording():
    """Keeps every :func:`build_span` opened inside it; yields the list."""
    spans: list = []
    token = _recording.set((spans, []))
    try:
        yield spans
    finally:
        _recording.reset(token)


@contextlib.contextmanager
def build_span(name: str):
    rec = _recording.get()
    open_names = rec[1] if rec is not None else []
    parent = open_names[-1] if open_names else None
    open_names.append(name)
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        open_names.pop()
        if rec is not None:
            rec[0].append((name, start, time.perf_counter_ns(), parent))
