"""mfu: model FLOP/s utilisation of the window, in percent: tokens per
second (host clock, untraced window) times the forward and backward FLOPs a
token needs (``flops.model_flops_per_token``, recompute not counted), over
the chips' bf16 peak."""

import flops


def read(ctx):
    per_token = flops.model_flops_per_token(ctx.m, ctx.traffic["seq_len"])
    peak = ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * ctx.tokens_per_s * per_token / peak
