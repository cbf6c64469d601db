"""Scheduler: 90th percentile of the wait from a request's due time to
the start of the tick that admits it, over the requests due in the
window before the tracer starts, which slows the host; a request still
waiting then counts its wait so far (harness clock).  Moves
``ttft_p90_ms``."""

import numpy as np


def read(r):
    w = r.window
    end = w.t_counted
    waits = [min(q.admitted if q.admitted is not None else end, end)
             - q.due for q in w.reqs.values() if w.t0 <= q.due < end]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
