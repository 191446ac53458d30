"""masked_request_share (%): the share of the window's emulated requests
that rode a dispatch in a lane whose weak-row filter mask was off (the
``masked_requests`` count of the program's ``emu.dispatch`` spans) over
the requests those dispatches emulated: 100 x sum masked / sum requests.
A base-vs-reduced grid that runs both arms in one dispatch per bucket
reads 50; a program whose dispatches do not count masked lanes reads
nothing."""
from bench.lib import program_spans


def read(ctx):
    tot = program_spans.dispatch_totals()
    if tot is None or not tot["requests"]:
        return None
    counts = [r.counts for r in program_spans.records()
              if r.name == "emu.dispatch"]
    if any("masked_requests" not in c for c in counts):
        return None
    return 100.0 * sum(c["masked_requests"] for c in counts) / tot["requests"]
