"""lane_fill (%): the share of executed lane-slots (each dispatch's
slots x padded lanes, from the program's ``emu.dispatch`` spans) that
the bound of two slots a request could use: 100 x 2 x requests /
lane-slots. Padding lanes, length skew within a group and the slot
budget's slack all lower it."""
from bench.lib import program_spans


def read(ctx):
    tot = program_spans.dispatch_totals()
    if tot is None or not tot["lane_slots"]:
        return None
    return 100.0 * 2 * tot["requests"] / tot["lane_slots"]
