"""Kernels: the least time the attention and SwiGLU work of the traced
decode steps needs on this chip (real contexts, bf16 bytes), over the
device time of the Mosaic kernels inside the decode-step program.
Moves ``tokens_per_s``."""


def read(r):
    t = r.trace
    steps = [k.contexts for k in r.traced_ticks() if k.contexts]
    if t is None or not steps or not t.kernel_seconds("decode"):
        return None
    need = sum(r.work.least_time(r.work.decode_kernel_calls(r.model, c),
                                 r.peak) for c in steps)
    return 100.0 * need / t.kernel_seconds("decode")
