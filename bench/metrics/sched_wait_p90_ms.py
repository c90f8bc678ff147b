"""90th percentile of the time from submission to the scheduler's first
admission of a request to a slot: ``admit_time - submit_time``, both
stamped on the request by the program, on its scheduler's clock. Over the
requests due in the window that were admitted in it: in a traced run the
profiler's stop lies between the close and the next step, so the wait of
a request admitted after the close holds it. Those left out are counted on
standard error. A program that stamps no ``admit_time`` reports nothing."""
import sys

import numpy as np


def read(ctx):
    waits, left_out = [], 0
    for o in ctx.obs.values():
        if (o.due is None or o.due < ctx.t_open or not o.tokens
                or getattr(o.req, "admit_time", None) is None):
            continue
        if o.tokens[0][0] <= ctx.t_close or (
                o.bound is not None and o.bound <= ctx.t_close):
            waits.append(o.req.admit_time - o.req.submit_time)
        else:
            left_out += 1
    if left_out:
        print(f"sched_wait_p90_ms: left out {left_out} of "
              f"{left_out + len(waits)} requests due in the window, "
              "admitted after the close", file=sys.stderr)
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 90))
