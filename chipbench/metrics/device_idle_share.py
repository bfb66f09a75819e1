"""device_idle_share: the share of the traced window, in percent, in which
no operation ran on the device (one minus the union of the device's
operation intervals over the window), averaged over the chips."""

import devtrace as trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    busy = [trace.busy_ns(ev, lo, hi) for ev in ctx.trace.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
