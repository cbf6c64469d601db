"""Model step: device time of the decode-step program per decode step,
from the profiler trace.  Moves ``tokens_per_s``."""


def read(r):
    t = r.trace
    if t is None or not t.module_count("decode"):
        return None
    return t.module_seconds("decode") / t.module_count("decode") * 1e3
