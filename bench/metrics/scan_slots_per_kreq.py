"""scan_slots_per_kreq (slots/kreq): scan slots the window's dispatches
ran (each dispatch's scan length, the ``slots`` count of the program's
``emu.dispatch`` spans) per thousand requests they emulated. Grouping,
bucketing and batch width move it; the scan body does not."""
from bench.lib import program_spans


def read(ctx):
    tot = program_spans.dispatch_totals()
    if tot is None or not tot["requests"]:
        return None
    return 1000.0 * tot["slots"] / tot["requests"]
