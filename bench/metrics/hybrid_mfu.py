"""The whole step's share of the peak in a hybrid cell: useful model
operations over the window (``bench.lib.costs.useful_flops``, every matmul
parameter, attention over the live context and the head), plus the
recurrent mixers' operations outside their matmul parameters (the
architecture's ``recurrence_flops``), over the traced window times the
chip's peak. An architecture without recurrent mixers reports nothing."""
from bench.lib import costs, spec


def read(ctx):
    arch = spec.architecture(ctx.cfg["model_type"])
    recurrence = getattr(arch, "recurrence_flops", None)
    if recurrence is None:
        return None
    tokens = sum(k for t in ctx.ticks for _, k in t.chunks)
    flops = (costs.useful_flops(ctx.ticks, ctx.shape)
             + recurrence(ctx.cfg, tokens))
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops"])
