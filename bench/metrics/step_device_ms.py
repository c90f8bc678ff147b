"""Device time of one serving step: the trace's time of the jitted step
program over its executions in the window."""
from bench.lib.names import is_step


def read(ctx):
    secs, n = ctx.trace.module_time(is_step)
    return 1e3 * secs / n if n else None
