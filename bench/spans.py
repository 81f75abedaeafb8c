"""The program's own host spans in a traced window.

The drive loop opens host spans (``jax.profiler.TraceAnnotation``) on
the thread that holds the window's span: ``plug.run`` >
``plug.iteration`` > ``plug.poll``, ``plug.dispatch``, ``plug.fetch``,
then ``plug.result``.  A trace of a program without them (an older
commit) holds none of these names, and :func:`idle_by_span` then
returns None.
"""
from __future__ import annotations

from bench import tracing

#: the prefix of every span the program names
PREFIX = "plug."
#: the drive loop's per-iteration span and its children
ITERATION = ("plug.iteration", "plug.poll", "plug.dispatch", "plug.fetch")


def idle_by_span(trace) -> dict | None:
    """Device-idle seconds of chip 0 in the window by the innermost
    ``plug.*`` host span open at each gap's midpoint (the window's own
    span where none is)."""
    spans = [(s, e, name.split("#")[0]) for s, e, name in trace.host
             if name.startswith(PREFIX)]
    if not spans or not trace.devices:
        return None
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in tracing.busy_intervals(trace.devices[0], lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    total: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2
        # spans of one thread nest: the innermost open one starts last
        # (of two that start together, the one that ends first)
        open_at = [(s, -e, name) for s, e, name in spans if s <= mid < e]
        inner = max(open_at)[2] if open_at else tracing.WINDOW
        total[inner] = total.get(inner, 0.0) + (b - a) * 1e-9
    return total
