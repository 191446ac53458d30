"""rc_request_share (%): the share of the window's emulated requests
that are in-DRAM RowClone ops (the ``rc_requests`` count of the
program's ``emu.dispatch`` spans: RC_COPY and RC_INIT) over the
requests those dispatches emulated: 100 x sum rc / sum requests. A
RowClone grid is a few dozen in-DRAM ops among tens of thousands of
loads and stores; a program whose dispatches do not count them reads
nothing."""
from bench.lib import program_spans


def read(ctx):
    tot = program_spans.dispatch_totals()
    if tot is None or not tot["requests"]:
        return None
    counts = [r.counts for r in program_spans.records()
              if r.name == "emu.dispatch"]
    if any("rc_requests" not in c for c in counts):
        return None
    return 100.0 * sum(c["rc_requests"] for c in counts) / tot["requests"]
