"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A TPU trace (``*.xplane.pb``) has one plane per chip, ``/device:TPU:<i>``,
whose ``XLA Ops`` line holds every operation the chip ran, named by its
HLO text, and a ``/host:CPU`` plane with a line per host thread; the
line named after the interpreter's executable (``python3``) holds the
main thread's Python frames as nested spans.  Host and device events share one
clock.  The harness wraps its measured window in a host span named
:data:`WINDOW`; everything here is clipped to that span.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the host span around the measured window
WINDOW = "bench.window"
#: what marks a Pallas kernel in an operation's HLO text
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    """Events of one traced window, in nanoseconds on the host's clock.

    ``devices`` holds one list of ``(start, end, name)`` operations per
    chip; ``host`` the Python frames as ``(start, end, name)``.
    """

    window: tuple[float, float]
    devices: list[list[tuple[float, float, str]]]
    host: list[tuple[float, float, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(log_dir: str) -> Trace:
    """Reads every ``.xplane.pb`` under ``log_dir`` (one trace)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    devices, lines = [], {}
    for path in files:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(DEVICE_PLANE):
                devices.append([(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name)
                                for line in plane.lines
                                if line.name == OPS_LINE
                                for e in line.events])
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    lines.setdefault(line.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
    # the Python frames are on the line that holds the window's span
    found = [(ev[:2], evs) for evs in lines.values() for ev in evs
             if ev[2] == WINDOW]
    if len(found) != 1:
        raise ValueError(
            f"trace has {len(found)} {WINDOW!r} spans in {len(files)} "
            "files; host lines: "
            + ", ".join(f"{k} ({len(v)})" for k, v in lines.items()))
    window, frames = found[0]
    return Trace(window=window, devices=devices, host=frames)


def busy_intervals(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the operations' intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for start, end, _ in sorted(ops):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    lo, hi = trace.window
    per_chip = [sum(b - a for a, b in busy_intervals(ops, lo, hi))
                for ops in trace.devices]
    return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0


def op_seconds(trace: Trace, mark: str) -> float:
    """Seconds of the operations whose name holds ``mark`` (clipped to
    the window), averaged over the chips."""
    lo, hi = trace.window
    per_chip = [sum(max(0.0, min(e, hi) - max(s, lo))
                    for s, e, name in ops if mark in name)
                for ops in trace.devices]
    return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0


def _short(name: str, width: int = 160) -> str:
    return " ".join(name.split())[:width]


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time in the window,
    as ``[name, seconds]`` averaged over the chips."""
    lo, hi = trace.window
    total: dict[str, float] = {}
    for ops in trace.devices:
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = _short(name)
                total[key] = total.get(key, 0.0) + d
    n = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """Device idle time in the window by what the host was doing.

    Each gap between busy intervals of chip 0 is charged to the innermost
    host frame that spans the gap's midpoint (the window span itself when
    no frame does).  Returns the ``k`` largest ``[frame, seconds]``.
    """
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = busy_intervals(trace.devices[0], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # the frames are one thread's call stack: they nest, so a
    # sweep over the gaps' midpoints with a stack of open frames finds
    # the innermost frame at each
    frames = sorted((s, -e, name) for s, e, name in trace.host
                    if e > lo and s < hi)
    total: dict[str, float] = {}
    stack: list[tuple[float, str]] = []
    i = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while i < len(frames) and frames[i][0] <= mid:
            start, neg_end, name = frames[i]
            while stack and stack[-1][0] < start:
                stack.pop()
            stack.append((-neg_end, name))
            i += 1
        while stack and stack[-1][0] < mid:
            stack.pop()
        inner = stack[-1][1] if stack else WINDOW
        total[inner] = total.get(inner, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[_short(name), ns * 1e-9] for name, ns in ranked]
