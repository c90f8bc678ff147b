"""90th percentile of the time from the scheduler's first admission of a
request to its first generated token: ``first_token_time - admit_time``,
both stamped on the request by the program, on its scheduler's clock. Over
the requests due in the window whose span does not hold the profiler's
stop, which in a traced run lies between the close and the next step: a
request admitted in the window with its first token after the close is
left out. Those are the longest prompts due late in the window, so the
percentile reads low; their count and prompt lengths go to standard error.
A program that stamps neither reports nothing."""
import sys

import numpy as np


def read(ctx):
    spans, left_out = [], []
    for o in ctx.obs.values():
        if (o.due is None or o.due < ctx.t_open or not o.tokens
                or getattr(o.req, "first_token_time", None) is None):
            continue
        if o.tokens[0][0] <= ctx.t_close or (
                o.bound is not None and o.bound > ctx.t_close):
            spans.append(o.req.first_token_time - o.req.admit_time)
        else:
            left_out.append(len(o.req.tokens))
    if left_out:
        print(f"prefill_p90_ms: left out {len(left_out)} of "
              f"{len(left_out) + len(spans)} requests due in the window, "
              "admitted before the close with the first token after it; "
              f"prompts {sorted(left_out)} tokens", file=sys.stderr)
    if not spans:
        return None
    return 1e3 * float(np.percentile(spans, 90))
