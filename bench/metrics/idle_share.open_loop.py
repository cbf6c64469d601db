"""Device: share of the traced interval in which no operation runs on
the chip, in an open-loop cell, where the host and the arrivals set the
pace.  Moves ``ttft_p90_ms``."""


def read(r):
    t = r.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
