"""The program's own spans (``repro.obs``) inside a run's window, for the
per-layer metrics that read them.

The program keeps its finished spans in a bounded ring on
``time.perf_counter_ns``, the clock of ``run.window_t0`` and
``run.window_t1``. A span that straddles an edge of the window counts with
the part of it inside, and its counts in the same proportion. A program
that keeps no spans, or whose ring let go of spans that ended inside the
window, gives no reading.
"""
from __future__ import annotations


def window(run):
    """The spans that overlap the window, as ``(span, t0, t1)`` with the
    times clipped to it (ns), or None (see the module's docstring)."""
    try:
        from repro import obs
    except ImportError:  # a program without spans
        return None
    w0, w1 = int(run.window_t0 * 1e9), int(run.window_t1 * 1e9)
    if w1 <= w0 or obs.oldest_ns() > w0:
        return None
    out = []
    for s in obs.spans():
        a, b = max(s.t0, w0), min(s.t1, w1)
        if a < b or (a == b and s.t0 == s.t1):
            out.append((s, a, b))
    return out


def _share(s, a, b) -> float:
    d = s.t1 - s.t0
    return (b - a) / d if d > 0 else 1.0


def count(clipped, name: str, key: str) -> float:
    """The sum of count ``key`` over the spans called ``name``."""
    return sum(getattr(s, key, 0) * _share(s, a, b)
               for s, a, b in clipped if s.name == name)


def seconds(clipped, name: str) -> float:
    """The summed time of the spans called ``name`` inside the window."""
    return sum(b - a for s, a, b in clipped if s.name == name) / 1e9


def union_seconds(intervals) -> float:
    """The time covered by ``(t0, t1)`` intervals (ns), overlaps once."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def gbps(run, name: str):
    """Bytes over time of the spans called ``name``: their ``bytes`` counts
    over their summed time, in GB/s (1e9 B/s)."""
    clipped = window(run)
    if clipped is None:
        return None
    t = seconds(clipped, name)
    if t <= 0:
        return None
    return count(clipped, name, "bytes") / t / 1e9
