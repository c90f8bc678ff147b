"""The paged attention kernel's share of its roofline: the least time for
the operations and bytes of the LIVE context that every step of the window
needed (``bench.lib.costs``), over the kernel's device time. A kernel that
streams pages the queries cannot see reads low."""
from bench.lib import costs
from bench.lib.names import is_attention


def read(ctx):
    secs, n = ctx.trace.op_time(is_attention)
    if not n:
        return None
    # one kernel launch per layer and step: the bound per step is the
    # larger of its two terms, summed over steps
    least = sum(
        costs.least_time(
            sum(costs.attn_flops(ctx.shape, a, k) for a, k in t.chunks),
            sum(costs.attn_bytes(ctx.shape, a, k) for a, k in t.chunks),
            ctx.peak)
        for t in ctx.ticks if t.chunks)
    return 100.0 * least / secs
