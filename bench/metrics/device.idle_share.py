"""device.idle_share: the share of the traced window, in %, in which no
operation ran on a chip, averaged over the cell's chips."""
from benchlib import trace as tr


def read(ctx):
    if not any(ctx.trace.ops.get(d) for d in ctx.devices):
        return None
    busy = sum(tr.busy_s(ctx.trace, d) for d in ctx.devices) / len(ctx.devices)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
