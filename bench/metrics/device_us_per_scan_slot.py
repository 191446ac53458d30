"""device_us_per_scan_slot (us/slot): device busy time of the window's
XLA programs (the busy union, summed over the cell's devices) per scan
slot each device ran (slots x shards over the program's
``emu.dispatch`` spans). The cost of one slot of the scan body; times
scan_slots_per_kreq / 1000 it gives scan_device_us_per_req on one
chip."""
from bench.lib import program_spans


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_ns"]:
        return None
    tot = program_spans.dispatch_totals()
    if tot is None or not tot["device_slots"]:
        return None
    return sum(tr["busy_ns"].values()) / 1e3 / tot["device_slots"]
