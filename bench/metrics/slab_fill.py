"""Share of the step slab's positions that hold a real token: (prompt
tokens + decode tokens computed) / (steps x slots x prefill_chunk), over the
window's dispatches. Read from the harness's record of every step."""


def read(ctx):
    steps = sum(t.dispatched for t in ctx.ticks)
    if not steps:
        return None
    used = sum(n for t in ctx.ticks for _, n in t.chunks)
    return 100.0 * used / (steps * ctx.num_slots * ctx.chunk)
