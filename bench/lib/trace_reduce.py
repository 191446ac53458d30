"""Reduce a profiler trace of the measured window to numbers.

Input is what :mod:`bench.lib.xplane` extracts: per device, the
intervals ``(start_ns, end_ns, name)`` in which an operation ran, and
the harness's own host spans ``(name, start_ns, end_ns)``; all on one
clock. Pure Python, so the arithmetic is checked on a small recorded
trace in ``tests/``.
"""
from __future__ import annotations


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return float(sum(e - s for s, e in union(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list:
    """Idle [start, end) stretches of the window between busy ones."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans, t: float) -> str:
    """Name of the harness span that covers instant ``t`` (the latest
    to start, if several do), else ``"between-spans"``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "between-spans"


def window_of(spans):
    """The measured window on the trace's clock: from the first span's
    start to the last span's end."""
    return min(s for _, s, _ in spans), max(e for _, _, e in spans)


def reduce(devices: dict, spans: list, top: int = 10) -> dict:
    """``devices``: {device name: [(start_ns, end_ns, op name), ...]}.
    Returns the window, each device's busy nanoseconds, the mean idle
    share, the ``top`` operations by device time (summed over devices)
    and the ``top`` longest idle gaps, each labelled with the host span
    the harness was in at its midpoint."""
    lo, hi = window_of(spans)
    window = float(hi - lo)
    busy = {d: busy_ns(ev, lo, hi) for d, ev in devices.items()}
    op_time: dict = {}
    for ev in devices.values():
        for s, e, name in ev:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
    all_gaps = []
    for d, ev in devices.items():
        for s, e in gaps(ev, lo, hi):
            all_gaps.append((span_at(spans, (s + e) / 2.0), float(e - s)))
    all_gaps.sort(key=lambda g: -g[1])
    n = len(devices)
    ok = n > 0 and window > 0
    return {
        "window_ns": window,
        "busy_ns": busy,
        "busy_mean_ns": sum(busy.values()) / n if n else 0.0,
        "idle_share": 1.0 - sum(busy.values()) / (n * window) if ok else None,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": all_gaps[:top],
    }
