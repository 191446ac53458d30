"""scan_device_us_per_req (us/req): device busy time of the window's XLA
programs (the busy union, summed over the cell's devices) per request
emulated in the window, the same count as emu_req_per_s."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_ns"] or not ctx.get("requests"):
        return None
    return sum(tr["busy_ns"].values()) / 1e3 / ctx["requests"]
