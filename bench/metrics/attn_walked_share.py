"""Share of the paged attention kernel's table entries that it walks: the
program's counters ``attn_blocks_walked`` (blocks up to each slot's last
live key, one layer) over ``attn_blocks_listed`` (entries the block tables
hold), summed over the window's steps from ``Tick.counters``. The rest are
dead entries the kernel skips. A program that counts neither reports
nothing."""


def read(ctx):
    walked = sum(t.counters.get("attn_blocks_walked", 0) for t in ctx.ticks)
    listed = sum(t.counters.get("attn_blocks_listed", 0) for t in ctx.ticks)
    if not listed:
        return None
    return 100.0 * walked / listed
