"""flash_fwd_roofline: the flash-attention forward kernel's share of its
roofline, in percent: the least time its calls could take on this chip
(``flops.flash_fwd`` from its shapes, ``flops.least_seconds``) over their
summed device time in the traced window, over all chips.  Recomputed
forwards count as calls of the kernel like any other."""

import flops
import devtrace as trace


def read(ctx):
    if ctx.trace is None:
        return None
    m, t = ctx.m, ctx.traffic
    data, model = ctx.mesh
    shape = (t["batch"] // data, m["num_attention_heads"] // model,
             m["num_key_value_heads"] // model, t["seq_len"], m["head_dim"])
    least = flops.least_seconds(flops.flash_fwd(*shape), ctx.peak)
    calls = spent = 0
    for events in ctx.trace.devices.values():
        n, ns = trace.kernel_time(events, ["fwd"], ctx.trace.lo, ctx.trace.hi,
                                       ctx.trace.kinds)
        calls, spent = calls + n, spent + ns * 1e-9
    return 100.0 * calls * least / spent if calls else None
