"""Model step: model FLOPs of the prompts prefilled in the traced ticks,
at their real lengths, over the prefill programs' device time times the
chip's bf16 peak.  Moves ``ttft_p90_ms``."""


def read(r):
    t = r.trace
    prompts = [p for k in r.traced_ticks() for p in k.prefills]
    if t is None or not prompts or not t.module_seconds("prefill"):
        return None
    flops = sum(r.work.prefill_flops(r.model, p) for p in prompts)
    return 100.0 * flops / (t.module_seconds("prefill")
                            * r.peak["bf16_flops_per_s"])
