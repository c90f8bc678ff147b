"""Device time of the adapter store's ``graph_mix`` kernel per store
``update()`` in the window (an update mixes twice: the new iterate, then
the served copy)."""
from bench.lib.names import is_graph_mix


def read(ctx):
    updates = sum(t.updates for t in ctx.ticks)
    secs, n = ctx.trace.op_time(is_graph_mix)
    if not updates or not n:
        return None
    return 1e3 * secs / updates
