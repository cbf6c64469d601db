"""Model step: model FLOPs of every prompt prefilled and every output
token decoded in the window before the tracer starts, at their real
lengths and contexts, over those seconds times the chip's bf16 peak.
Moves ``tokens_per_s``."""


def read(r):
    work, m = r.work, r.model
    flops = 0.0
    for k in r.counted_ticks():
        flops += sum(work.prefill_flops(m, p) for p in k.prefills)
        flops += sum(work.decode_flops(m, c) for c in k.contexts)
    if not flops:
        return None
    return 100.0 * flops / (r.counted_s * r.peak["bf16_flops_per_s"])
