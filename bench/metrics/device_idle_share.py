"""device_idle_share (%): the share of the traced window in which no
XLA operation ran on the device, 1 - busy union / window, averaged over
the cell's devices (each device's value is printed on stderr)."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["idle_share"] is None:
        return None
    return 100.0 * tr["idle_share"]
