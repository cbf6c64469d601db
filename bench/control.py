"""Readings that set a cell's limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]

One process builds and warms the cell's engine once, then for each seed
draws that seed's weights, serves a short window at the cell's own load
through ``Engine.run``, and compares a sample of the finished requests
with the plain reference as a benchmark run does.  For the first
``--control-seeds`` seeds it also reads the control: the family's
reference (``references/<family>.py``) computed with fp8 matmul
operands, put in the program's place.  Both readings go through the
benchmark's own decision (``harness.numbers`` and ``harness.passes``):
the program's has to come out correct, the control's not.  One JSON
line per seed.  The benchmark's own runs never run the control.  Needs
a TPU, as ``run.py`` does.
"""

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def readings(engine, cell, like, seed: int, seconds: float, control: bool):
    """Serve one seed's window on ``engine`` and read its gaps."""
    from bench import check, harness, weights

    engine.params = None
    engine.params = weights.make(like, seed)
    engine.slots = [None] * engine.max_batch
    engine.queue.clear()
    w = harness.serve(engine, cell, seed, seconds)
    picked = check.sample(harness.checked(w), seed,
                          harness.check_tokens(cell))
    gap, gap_c, n = check.widest_gaps(cell.reference, cell.config["model"],
                                      engine.params, picked, control=control)
    nums = harness.numbers(cell, w, gap, n)
    out = {"seed": seed, "widest_gap": gap, "tokens_compared": n,
           "requests_compared": len(picked),
           "correct": harness.passes(nums), "check": nums}
    if control:
        nums_c = harness.numbers(cell, w, gap_c, n)
        out.update(control_gap=gap_c, control_correct=harness.passes(nums_c))
    return out


def record(engine, cell, seed: int, path: Path) -> None:
    import shutil

    from bench import harness, trace

    tdir = harness.CACHE / "trace" / "record"
    shutil.rmtree(tdir, ignore_errors=True)
    w = harness.serve(engine, cell, seed, 2 * harness.TRACE_S,
                      trace_s=1.0, trace_dir=tdir)
    ex = trace.trim(trace.load(tdir), 2)
    shutil.rmtree(tdir, ignore_errors=True)
    a = w.traced[0]
    ex["ticks"] = [{"contexts": k.contexts, "prefills": k.prefills}
                   for k in w.ticks[a:a + 2]]
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(ex, f)
    engine.slots = [None] * engine.max_batch
    engine.queue.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--record-trace", type=Path, default=None,
                    help="also trace a window of the first seed and write "
                         "its first two ticks here, extracted (a test "
                         "fixture)")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.configure_caches()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    engine = harness.build_engine(cell.config, cell.mix, args.seeds[0])
    harness.warm(engine, cell.config["model"]["vocab_size"])
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        engine.params)
    print(f"control: set-up {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    if args.record_trace:
        record(engine, cell, args.seeds[0], args.record_trace)
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        out = readings(engine, cell, like, seed, args.seconds,
                       i < args.control_seeds)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
