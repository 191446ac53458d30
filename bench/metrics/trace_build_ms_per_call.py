"""trace_build_ms_per_call (ms/call): host time a technique spent
building its grid's traces and profiling clonability inside the user's
call (the program's ``tech.traces`` spans), per top-level ``emu.call``.
It reads nothing where the program records no such span, or where the
builds and the calls do not pair up one to one."""
from bench.lib import program_spans


def read(ctx):
    recs = program_spans.records()
    if recs is None:
        return None
    builds = [r.end_ns - r.start_ns for r in recs if r.name == "tech.traces"]
    calls = sum(1 for r in recs if r.name == "emu.call" and r.parent is None)
    if not builds or len(builds) != calls:
        return None
    return sum(builds) / 1e6 / calls
