"""Host time of the copy of each dispatch's outputs (the step's logits or
tokens) from the device: the mean length of the program's ``serve.fetch``
spans in the window, one per dispatch, which begin once the outputs are
ready on the device. A trace without them reports nothing."""

SPAN = "serve.fetch"


def read(ctx):
    spans = [s.dur for s in ctx.trace.program_spans if s.name == SPAN]
    if not spans:
        return None
    return 1e-6 * sum(spans) / len(spans)
