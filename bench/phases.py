"""Engine phases, named kernels and request timelines in a traced window.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        [--record <path>] [--record-ticks <k>]

One benchmark run with ``--trace 1`` (``harness.run``) that also reads
what the serving program records about itself, which the benchmark's
per-layer readers do not read:

* ``idle_by_phase``: the traced interval's idle seconds, split exactly
  by the innermost engine span over them (``engine.tick``,
  ``engine.sync.sample``, ...; "outside the engine" where none is open);
* ``sync_idle_share``: % of the traced interval in which no operation
  runs on the chip while the host is inside an ``engine.sync.*`` span;
* ``kernels``: count and device seconds of each named Pallas kernel
  kind (``attention``, ``rmsnorm_swiglu``) by program, and
  ``named_share``, their share of each program's Mosaic time;
* ``decode_attention_roofline``, ``decode_swiglu_roofline``: the least
  time the traced decode steps' attention (SwiGLU) work needs on the
  chip (``work/<family>.py``) over those kernels' device time;
* ``engine_queue_wait_p90_ms``: 90th percentile of the wait from a
  request's due time to the start of its own prefill as the engine
  stamps it, over the requests due in the window before the tracer
  starts; a request still waiting then counts its wait so far;
* ``traced_tick_ms``: mean wall time of the traced ticks.

A program without engine spans, kernel names or request timelines reads
null or empty for each.  Prints the run's result line with these added
under ``"phases"``, as the last line of standard output.  ``--record``
also writes the extracted trace of the first ``--record-ticks`` traced
ticks (``trace.trim``), with their contexts and prompt lengths, as a
test fixture.  Needs a TPU, as ``run.py`` does.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness, trace  # noqa: E402

ENGINE, SYNC = "engine.", "engine.sync."
OUTSIDE = "outside the engine"
KERNELS = ("attention", "rmsnorm_swiglu")   # the named Pallas kernel kinds


def kernel_kind(op: str) -> Optional[str]:
    """``rmsnorm_swiglu_2`` (group 2 of a program that emits several)
    -> ``rmsnorm_swiglu``; None for an op that is no named kernel."""
    kind = re.sub(r"_\d+$", "", op)
    return kind if kind in KERNELS else None


def pieces(host, w0: float, w1: float) -> List[tuple]:
    """The innermost engine span at each moment of ``[w0, w1)``, as
    contiguous ``(start, end, name)`` pieces (``OUTSIDE`` where none is
    open).  The engine's spans nest, all on one thread."""
    spans = sorted(((s, s + d, n) for n, s, d in host
                    if n.startswith(ENGINE) and s < w1),
                   key=lambda x: (x[0], -x[1]))
    out: List[tuple] = []
    stack: List[tuple] = []             # (end, name), innermost last
    t = w0

    def advance(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if to > t:
            out.append((t, to, stack[-1][1] if stack else OUTSIDE))
            t = to

    for s, e, n in spans:
        advance(s)
        stack.append((e, n))
    advance(w1)
    return [(a, min(b, w1), n) for a, b, n in out if a < w1]


def reduce(ex: dict) -> dict:
    """Idle seconds by engine phase, idle seconds inside the engine's
    syncs, and the named kernels' ``[count, seconds]`` by
    ``"<program>:<kind>"``, of an extracted trace (``trace.load``),
    averaged over the TPUs as ``trace.summarize`` does."""
    spans = [e for e in ex["host"] if e[0] in trace.HOST_SPANS]
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    n_dev = len(ex["devices"])
    ps = pieces(ex["host"], w0, w1)
    starts_p = [p[0] for p in ps]
    by_phase: Dict[str, float] = defaultdict(float)
    named: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for dev in ex["devices"]:
        mods = sorted((s, s + d, trace.MODULES.get(n, "other"))
                      for n, s, d in dev["modules"] if w0 <= s < w1)
        starts = [m[0] for m in mods]
        ops = [o for o in dev["ops"] if w0 <= o[1] < w1]
        for name, s, d, mosaic in ops:
            kind = kernel_kind(name)
            if mosaic and kind is not None:
                # the program an op belongs to, as trace.summarize finds it
                i = bisect.bisect_right(starts, s) - 1
                key = f"{mods[i][2] if i >= 0 else 'other'}:{kind}"
                named[key][0] += 1 / n_dev
                named[key][1] += d * 1e-9 / n_dev
        busy = trace._union(
            [(max(s, w0), min(e, w1)) for s, e, _ in mods]
            + [(max(s, w0), min(s + d, w1)) for _, s, d, _ in ops])
        edges = [w0] + [x for s_e in busy for x in s_e] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            i = bisect.bisect_right(starts_p, a) - 1
            while b > a and i < len(ps) and ps[i][0] < b:
                s, e, name = ps[i]
                if e > a:
                    by_phase[name] += (min(b, e) - max(a, s)) * 1e-9 / n_dev
                i += 1
    engine = any(p[2] != OUTSIDE for p in ps)
    phases = sorted(by_phase.items(), key=lambda kv: -kv[1])
    return {"idle_by_phase": [[k, v] for k, v in phases] if engine else [],
            "sync_idle_s": sum(v for k, v in phases if k.startswith(SYNC))
            if engine else None,
            "named": {k: v for k, v in sorted(named.items())}}


def _roofline(rd, named: dict, kind: str, call) -> Optional[float]:
    sec = named.get(f"decode:{kind}", [0, 0.0])[1]
    steps = [k.contexts for k in rd.traced_ticks() if k.contexts]
    if not steps or not sec:
        return None
    layers = rd.model["num_hidden_layers"]
    need = sum(layers * rd.work.least_time([call(rd.model, c)], rd.peak)
               for c in steps)
    return 100.0 * need / sec


def engine_queue_wait_p90_ms(w, timelines: dict) -> Optional[float]:
    """p90 of due time to the engine-stamped start of each request's own
    prefill (see the module's docstring); None without timelines."""
    import numpy as np

    if not timelines:
        return None
    end = w.t_counted
    waits = []
    for q in w.reqs.values():
        if w.t0 <= q.due < end:
            tl = timelines.get(q.rid)
            waits.append((end if tl is None else min(tl.prefill_s, end))
                         - q.due)
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3


def readings(ex: Optional[dict], w, cell, peak: dict,
             timelines: dict) -> dict:
    """Everything this module reads, for one window and its trace."""
    out: dict = {"engine_queue_wait_p90_ms":
                 engine_queue_wait_p90_ms(w, timelines)}
    if ex is None:
        return out
    s = trace.summarize(ex)
    red = reduce(ex)
    rd = harness.Readings(cell, w, cell.work, peak, s)
    work = cell.work
    named = red["named"]
    share = {}
    for kind, (_, sec) in sorted(s.kernels.items()):
        got = sum(v[1] for k, v in named.items()
                  if k.startswith(kind + ":"))
        share[kind] = 100.0 * got / sec if sec else None
    ticks = rd.traced_ticks()
    out.update(
        idle_by_phase=red["idle_by_phase"],
        sync_idle_share=None if red["sync_idle_s"] is None
        else 100.0 * red["sync_idle_s"] / s.window_s,
        idle_share=100.0 * (1.0 - s.busy_s / s.window_s),
        kernels=named, named_share=share,
        decode_attention_roofline=_roofline(
            rd, named, "attention", work.decode_attention_call),
        decode_swiglu_roofline=_roofline(
            rd, named, "rmsnorm_swiglu",
            lambda m, c: work.swiglu_call(m, len(c))),
        traced_tick_ms=sum(k.t1 - k.t0 for k in ticks) / len(ticks) * 1e3
        if ticks else None)
    return out


@contextlib.contextmanager
def capture():
    """While open, ``harness.serve`` also keeps the window, the request
    timelines the engine reports, and the extracted trace (before
    ``harness.run`` deletes it) in the yielded dict."""
    seen: dict = {"timelines": {}, "trace": None, "window": None}
    serve = harness.serve

    def serve_and_keep(engine, *args, **kwargs):
        run = engine.run

        def run_and_keep(*a, **k):
            rep = run(*a, **k)
            for tl in getattr(rep, "timeline", ()):
                seen["timelines"][tl.rid] = tl
            return rep

        engine.run = run_and_keep
        w = serve(engine, *args, **kwargs)
        seen["window"] = w
        if w.t_trace is not None:
            seen["trace"] = trace.load(kwargs["trace_dir"])
        return w

    harness.serve = serve_and_keep
    try:
        yield seen
    finally:
        harness.serve = serve


def fixture(ex: dict, w, n_ticks: int) -> dict:
    """The extracted trace of the first ``n_ticks`` traced ticks, with
    their contexts and prompt lengths."""
    spans = sorted((e for e in ex["host"] if e[0] in trace.HOST_SPANS),
                   key=lambda e: e[1])
    n_spans = [e[0] for e in spans].index("bench.tick") + 1
    while (sum(e[0] == "bench.tick" for e in spans[:n_spans]) < n_ticks
           and n_spans < len(spans)):
        n_spans += 1
    out = trace.trim(ex, n_spans)
    a = w.traced[0]
    out["ticks"] = [{"contexts": k.contexts, "prefills": k.prefills}
                    for k in w.ticks[a:a + n_ticks]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=Path, default=None)
    ap.add_argument("--record-ticks", type=int, default=2)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.configure_caches()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"phases: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    with capture() as seen:
        result = harness.run(cell, args.seed, args.seconds, True, T_START)
    w = seen["window"]
    ph = readings(seen["trace"], w, cell, harness.peak_of(
        devices[0].device_kind), seen["timelines"])
    for k, v in ph.items():
        print(f"phases {k}: {v!r}", file=sys.stderr)
    if args.record and seen["trace"] is not None:
        args.record.parent.mkdir(parents=True, exist_ok=True)
        with open(args.record, "w") as f:
            json.dump(fixture(seen["trace"], w, args.record_ticks), f)
    result["phases"] = ph
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
