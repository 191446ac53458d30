"""The program's own span log (``repro.core.spans``), as the per-layer
readers see it: the spans the emulator recorded while the window's
profiler session was active. Every function returns None where the
program has no span log, recorded nothing, or its log cannot be
trusted whole (spans dropped at its cap, or dispatch counts that do not
add up to the calls' results), so a reader reports nothing rather than
raising or reading part of the window."""
from __future__ import annotations

# spans that wait on something else: the device (``emu.wait``), the
# prefetch thread (``emu.prefetch_wait``), or everything under them
# (``emu.call``); host_ms_per_dispatch leaves them out
WAITING = ("emu.call", "emu.wait", "emu.prefetch_wait")


def records():
    try:
        from repro.core import spans
    except ImportError:
        return None
    if spans.dropped():
        return None
    return spans.records() or None


def dispatch_totals():
    """Sums over the ``emu.dispatch`` spans: ``dispatches``, ``slots``,
    ``requests``, ``lane_slots`` (slots x padded lanes) and
    ``device_slots`` (slots x shards: the slots each device ran). None
    where the dispatches' ``requests`` differ from those the top-level
    ``emu.call`` spans returned: a dispatch counted twice (a retried
    task) or a call only partly inside the session."""
    recs = records()
    if recs is None:
        return None
    tot = dict.fromkeys(("dispatches", "slots", "requests", "lane_slots",
                         "device_slots"), 0)
    returned = 0
    for r in recs:
        c = r.counts
        if r.name == "emu.call" and r.parent is None:
            returned += c["requests"]
        if r.name != "emu.dispatch":
            continue
        tot["dispatches"] += 1
        tot["slots"] += c["slots"]
        tot["requests"] += c["requests"]
        tot["lane_slots"] += c["slots"] * c["lanes"]
        tot["device_slots"] += c["slots"] * c["shards"]
    if not tot["dispatches"] or tot["requests"] != returned:
        return None
    return tot


def host_ns():
    """Time summed over every span not in :data:`WAITING`, over all
    threads. None of those spans encloses another of them."""
    recs = records()
    if recs is None:
        return None
    return sum(r.end_ns - r.start_ns for r in recs if r.name not in WAITING)
