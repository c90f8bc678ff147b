"""The Mamba-2 mixers' share of their roofline: the least time for the
mixers' work in every step of the window, over the device time under the
name scope ``mamba2``. A step's work (the architecture's module,
``bench/arch/<model_type>.py``): the useful operations for its real tokens
(projections, conv, recurrence), every mixer's weights read once, and each
slot that computed a token reading and writing its state once. The slab's
padding rows are not useful work, so a step that computes them reads low."""
from bench.lib import costs, spec


def read(ctx):
    secs, n = ctx.trace.scope_time("mamba2")
    if not n:
        return None
    arch = spec.architecture(ctx.cfg["model_type"])
    weights = arch.mixer_weight_bytes(ctx.cfg)
    state = arch.mixer_state_bytes(ctx.cfg)
    least = sum(
        costs.least_time(
            arch.mixer_flops(ctx.cfg, sum(k for _, k in t.chunks)),
            weights + 2 * state * len(t.chunks), ctx.peak)
        for t in ctx.ticks if t.chunks)
    return 100.0 * least / secs
