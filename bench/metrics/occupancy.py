"""Scheduler: mean share of the ``max_batch`` slots that decode on a
tick, over the window's ticks that decode before the tracer starts
(harness count).  Moves ``tokens_per_s``."""


def read(r):
    used = [t.n_decode for t in r.counted_ticks() if t.n_decode]
    if not used:
        return None
    return 100.0 * sum(used) / (len(used) * r.max_batch)
