"""host_ms_per_dispatch (ms/dispatch): host time the program spent in
its own spans (planning, packing, stream window assembly, dispatch,
stream consume and finalize; none encloses another, so their durations
summed over threads), per ``emu.dispatch``. Waits on the device or on
the prefetch thread are left out (``bench.lib.program_spans.WAITING``)."""
from bench.lib import program_spans


def read(ctx):
    tot = program_spans.dispatch_totals()
    host = program_spans.host_ns()
    if tot is None or host is None:
        return None
    return host / 1e6 / tot["dispatches"]
