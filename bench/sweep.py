"""Rate sweep of an open-loop cell: where the queue starts to grow.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates <r> [<r> ...]

One process builds and warms the cell's engine once, then serves a
window of ``--seconds`` at each Poisson rate (requests/s) in turn,
through ``Engine.run`` as a benchmark run does.  For each rate it prints
one JSON line: the requests due, those admitted, the backlog (due and
not yet admitted) at the window's middle and at its close, and the
median and 90th percentile of the time to first token.  The knee is the
highest rate at which no backlog holds at the middle or at the close
(a backlog that drains by the close is a queue the rate built, not one
it sustains); a cell's mix file runs at 0.8 of it.  Needs a TPU, as
``run.py`` does.
"""

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def backlog(w, t: float) -> int:
    """Requests due by ``t`` and not admitted by then."""
    return sum(1 for r in w.reqs.values() if r.due <= t
               and (r.admitted is None or r.admitted > t))


def point(engine, cell, seed: int, seconds: float, rate: float) -> dict:
    from bench import harness

    mix = dict(cell.mix, rate_per_s=rate)
    engine.slots = [None] * engine.max_batch
    engine.queue.clear()
    w = harness.serve(engine, dataclasses.replace(cell, mix=mix), seed,
                      seconds)
    ttft = [(r.times[0] if r.times else w.t_close) - r.due
            for r in w.reqs.values()]
    e2e = harness.end_to_end(w)
    return {"rate_per_s": rate, "due": len(w.reqs),
            "admitted": sum(1 for r in w.reqs.values() if r.admitted),
            "backlog_mid": backlog(w, w.t0 + seconds / 2),
            "backlog_close": backlog(w, w.t_close),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "itl_p95_ms": e2e.get("itl_p95_ms"),
            "tokens_per_s": e2e["tokens_per_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    if cell.saturated:
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    harness.configure_caches()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    engine = harness.build_engine(cell.config, cell.mix, args.seed)
    harness.warm(engine, cell.config["model"]["vocab_size"])
    for rate in args.rates:
        print(json.dumps(point(engine, cell, args.seed, args.seconds, rate)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
