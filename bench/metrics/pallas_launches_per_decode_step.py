"""Compiler: Mosaic (Pallas) kernel launches inside the decode-step
program, per decode step, from the profiler trace.  Moves
``tokens_per_s``."""


def read(r):
    t = r.trace
    if t is None or not t.module_count("decode"):
        return None
    return t.kernel_count("decode") / t.module_count("decode")
