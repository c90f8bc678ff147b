"""KV blocks the allocator holds for requests over the pool's allocatable
blocks, averaged over the window's steps."""


def read(ctx):
    if not ctx.ticks:
        return None
    return 100.0 * sum(t.blocks for t in ctx.ticks) / (len(ctx.ticks)
                                                       * ctx.pool_blocks)
