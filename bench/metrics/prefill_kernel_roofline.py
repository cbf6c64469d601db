"""Kernels: the least time the attention and SwiGLU work of the prompts
prefilled in the traced ticks needs on this chip (real lengths, bf16
bytes), over the device time of the Mosaic kernels inside the prefill
programs.  Moves ``ttft_p90_ms``."""


def read(r):
    t = r.trace
    prompts = [p for k in r.traced_ticks() for p in k.prefills]
    if t is None or not prompts or not t.kernel_seconds("prefill"):
        return None
    need = sum(r.work.least_time(r.work.prefill_kernel_calls(r.model, p),
                                 r.peak) for p in prompts)
    return 100.0 * need / t.kernel_seconds("prefill")
