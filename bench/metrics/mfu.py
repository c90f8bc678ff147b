"""Useful model operations over the window, over the traced window times
the chip's peak (``bench.lib.costs.useful_flops``): the whole step's share
of the peak in the closed-loop cell."""
from bench.lib import costs


def read(ctx):
    flops = costs.useful_flops(ctx.ticks, ctx.shape)
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops"])
