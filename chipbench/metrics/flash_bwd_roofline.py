"""flash_bwd_roofline: the flash-attention backward's share of its
roofline, in percent: the least time of its dq and dk/dv kernels together
(``flops.flash_bwd``) times the backward calls, over the summed device time
of both kernels in the traced window, over all chips."""

import flops
import devtrace as trace


def read(ctx):
    if ctx.trace is None:
        return None
    m, t = ctx.m, ctx.traffic
    data, model = ctx.mesh
    shape = (t["batch"] // data, m["num_attention_heads"] // model,
             m["num_key_value_heads"] // model, t["seq_len"], m["head_dim"])
    least = flops.least_seconds(flops.flash_bwd(*shape), ctx.peak)
    calls = spent = 0
    for events in ctx.trace.devices.values():
        n_dq, ns_dq = trace.kernel_time(events, ["dq"], ctx.trace.lo, ctx.trace.hi,
                                       ctx.trace.kinds)
        n_dkv, ns_dkv = trace.kernel_time(events, ["dkv"], ctx.trace.lo, ctx.trace.hi,
                                       ctx.trace.kinds)
        calls, spent = calls + min(n_dq, n_dkv), spent + (ns_dq + ns_dkv) * 1e-9
    return 100.0 * calls * least / spent if calls else None
