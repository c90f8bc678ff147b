"""90th percentile, over the requests due in the window, of the time from
when a request was due to the start of the first step at which it held a
slot. Taken by the harness around ``step()``."""
import numpy as np


def read(ctx):
    waits = [o.bound - o.due for o in ctx.obs.values()
             if o.due is not None and o.due >= ctx.t_open
             and o.bound is not None]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 90))
