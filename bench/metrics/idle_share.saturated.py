"""Device: share of the traced interval in which no operation runs on
the chip, in a cell whose backlog never empties.  Moves
``tokens_per_s``."""


def read(r):
    t = r.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
