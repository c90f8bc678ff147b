"""Device time of the Mamba-2 mixers (the program's name scope ``mamba2``)
per execution of the step program in the window."""
from bench.lib.names import is_step


def read(ctx):
    secs, n = ctx.trace.scope_time("mamba2")
    _, steps = ctx.trace.module_time(is_step)
    if not n or not steps:
        return None
    return 1e3 * secs / steps
