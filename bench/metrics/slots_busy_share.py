"""Bound slots over all slots, averaged over the window's steps (counted
by the slot map after each step)."""


def read(ctx):
    if not ctx.ticks:
        return None
    return 100.0 * sum(t.live for t in ctx.ticks) / (len(ctx.ticks)
                                                     * ctx.num_slots)
