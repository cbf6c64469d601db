"""Model step: device time of the prefill programs per 1,000 real
prompt tokens prefilled in the traced ticks.  Moves ``ttft_p90_ms``."""


def read(r):
    t = r.trace
    tokens = sum(sum(k.prefills) for k in r.traced_ticks())
    if t is None or not tokens or not t.module_count("prefill"):
        return None
    return t.module_seconds("prefill") / tokens * 1e6
