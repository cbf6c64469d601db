"""Serving-loop benchmark: replay a fixed synthetic open-loop trace
through the continuous-batching engine (``launch/engine.py``) and emit
the gated numbers — tokens/sec, p50/p99 per-token latency, occupancy,
and the zero-recompile / zero-fallback / zero-degradation pins.

    PYTHONPATH=src:. python benchmarks/serve_bench.py --preset ci \
        --json SERVE_ci.json --report serve_report.json

Row format matches ``benchmarks/run.py`` (``name,us_per_call,derived``)
so ``check_regression.py`` gates ``serve_*`` rows the same way it gates
``pipeline_*`` rows: tokens/sec may not collapse >1.5x below the pinned
baseline, and any steady-state decode recompile or Pallas fallback
fails outright.  Deterministic keys (completed/rejected counts, compile
counts, and the resilience counters ``degradations``/``quarantined``,
which must be zero on the clean path) are pinned exactly.

Chaos mode (``--faults chaos``, the CI ``chaos`` job) runs the preset
twice against a throwaway kernel-cache dir — once clean, once under a
seeded ``resilience.FaultPlan`` injecting a Pallas compile failure at
the grouped AND ungrouped rungs (so the ladder is exercised down to the
jax rung) and one corrupted on-disk plan, with one NaN logits row
planted in the third decode step by wrapping the engine's ``_decode`` —
and gates internally:

* every non-poisoned request completes, tokens byte-identical to the
  clean run;
* ``degradations`` equals the number of compile faults in the plan,
  ``quarantined``/``corrupt_plans`` match the cache faults exactly, and
  ``n_poisoned`` is exactly 1;
* chaos tokens/sec stays within the same 1.5x collapse gate, measured
  against this runner's own clean pass.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile


PRESET_ARGS = {
    # tiny fixed trace for CI runners: small slot count, short prompts
    "ci": dict(arch="smollm-135m", backend="pallas", max_batch=2,
               max_len=64, prompt_buckets=(8, 16), n_requests=8,
               arrival_rate=1.0, prompt_lens=(4, 14),
               gen_lens=(3, 8), seed=0, keep_per_step=False),
    # the trajectory pin at repo root (BENCH_serve.json)
    "full": dict(arch="smollm-135m", backend="pallas", max_batch=4,
                 max_len=96, prompt_buckets=(8, 16, 32),
                 n_requests=32, arrival_rate=1.0,
                 prompt_lens=(4, 30), gen_lens=(6, 16), seed=0,
                 keep_per_step=False),
}


def _presets():
    from repro.launch.serve import ServeConfig
    return {k: ServeConfig(**v) for k, v in PRESET_ARGS.items()}


# the seeded chaos plan: one compile failure at the grouped AND the
# ungrouped rung (first compile of warmup -> ladder lands on jax) and
# the first on-disk plan read corrupted
def _chaos_plan():
    from repro import resilience as RZ
    return RZ.FaultPlan([
        RZ.FaultSpec(site="compile:grouped", indices=(0,), kind="raise",
                     message="chaos: grouped lowering down"),
        RZ.FaultSpec(site="compile:ungrouped", indices=(0,), kind="raise",
                     message="chaos: ungrouped lowering down"),
        RZ.FaultSpec(site="cache:get_plan", indices=(0,), kind="corrupt"),
    ], seed=0)


# the 0-based decode call after warm-up whose logits get one NaN row
POISON_DECODE_INDEX = 2


def _poison_decode(engine, index: int = POISON_DECODE_INDEX) -> None:
    """Wrap ``engine._decode`` so its ``index``-th call returns a NaN
    logits row for the first active slot; the engine's finite-logits
    guard must contain it to that one sequence."""
    import jax.numpy as jnp

    decode, calls = engine._decode, []

    def poisoned(params, caches, tokens, pos):
        logits, caches = decode(params, caches, tokens, pos)
        calls.append(1)
        if len(calls) - 1 == index:
            row = next(i for i, s in enumerate(engine.slots) if s)
            logits = logits.at[row, -1].set(jnp.nan)
        return logits, caches

    engine._decode = poisoned


def _row(preset: str, cfg, report) -> dict:
    total_tokens = report.prefill_tokens + report.decode_tokens
    us_per_token = (report.wall_s * 1e6 / max(report.decode_tokens, 1))
    derived = ";".join([
        f"tokens_per_s={report.tokens_per_s:.1f}",
        f"decode_tokens_per_s={report.decode_tokens_per_s:.1f}",
        f"ttft_p50_ms={report.ttft_p50_ms:.2f}",
        f"ttft_p99_ms={report.ttft_p99_ms:.2f}",
        f"itl_p50_ms={report.itl_p50_ms:.2f}",
        f"itl_p99_ms={report.itl_p99_ms:.2f}",
        f"mean_occupancy={report.mean_occupancy:.2f}",
        f"max_queue_depth={report.max_queue_depth}",
        f"steps={report.steps}",
        f"total_tokens={total_tokens}",
        f"completed={report.n_completed}",
        f"rejected={report.n_rejected}",
        f"stalled={report.n_evicted_stalled}",
        f"warmup_compiles={report.warmup_compiles}",
        f"decode_recompiles={report.decode_recompiles}",
        f"pallas_fallbacks={report.pallas_fallbacks}",
        f"degradations={report.degradations}",
        f"quarantined={report.quarantined}",
        f"poisoned={report.n_poisoned}",
        f"cache_hit_rate={report.cache_hit_rate:.3f}",
    ])
    return {"name": f"serve_{cfg.arch}_{preset}",
            "us_per_call": us_per_token, "derived": derived}


def bench(preset: str) -> dict:
    from repro.launch.serve import run
    cfg = _presets()[preset]
    report = run(cfg)
    return {"row": _row(preset, cfg, report), "report": report}


def chaos(preset: str) -> dict:
    """The chaos harness: clean pass, then the same preset under the
    seeded fault plan, gated against the clean pass.  Returns
    ``{"row", "report", "failures": [...]}`` — empty failures = pass."""
    from repro import pipeline, resilience as RZ
    from repro.launch.serve import build_engine, run

    cfg = _presets()[preset]
    cache_dir = tempfile.mkdtemp(prefix="repro-chaos-cache-")
    os.environ["REPRO_KERNEL_CACHE"] = cache_dir
    pipeline.reset_default_cache()

    clean = run(cfg)
    # drop every in-process kernel (the pipeline cache AND the model
    # layers' per-shape lru caches) but keep the on-disk plans, so the
    # faulted pass re-reads (and the plan corrupts) the disk entries and
    # re-runs every compile under the injected ladder faults
    from repro.models import layers
    layers._attention_kernel.cache_clear()
    layers._swiglu_kernel.cache_clear()
    pipeline.reset_default_cache()
    plan = _chaos_plan()
    with RZ.faults(plan):
        engine = build_engine(cfg)
        engine.warmup()
        _poison_decode(engine)
        faulted = run(cfg, engine)
    stats = pipeline.default_cache().stats

    failures = []

    def gate(ok: bool, what: str):
        if not ok:
            failures.append(what)

    poisoned = {f["rid"] for f in faulted.failures
                if f["reason"] in ("nonfinite_logits",
                                   "nonfinite_prefill")}
    n_nan = 1  # the row _poison_decode plants
    n_compile = plan.expected_count("compile:")
    n_cache = plan.expected_count("cache:")

    gate(plan.fired_count() == len(plan.specs),
         f"every planned fault fires (fired {plan.fired_count()}/"
         f"{len(plan.specs)}: {plan.fired})")
    gate(faulted.n_poisoned == n_nan,
         f"poisoned evictions match the plan "
         f"({faulted.n_poisoned} != {n_nan})")
    gate(faulted.n_completed == clean.n_completed - len(poisoned),
         f"all non-poisoned requests complete "
         f"({faulted.n_completed} != {clean.n_completed}-{len(poisoned)})")
    mismatched = [r for r in clean.tokens
                  if int(r) not in poisoned
                  and clean.tokens[r] != faulted.tokens.get(r)]
    gate(not mismatched,
         f"non-poisoned tokens byte-identical to the clean run "
         f"(mismatched rids {mismatched})")
    gate(faulted.degradations == n_compile,
         f"ladder demotions match the plan "
         f"({faulted.degradations} != {n_compile})")
    served_rungs = [s for s, _, _ in plan.fired if s.startswith("compile:")]
    gate({"compile:grouped", "compile:ungrouped"} <= set(served_rungs),
         f"ladder exercised down to the jax rung (fired {served_rungs})")
    gate(stats.corrupt_plans == n_cache,
         f"corrupt plans match the plan "
         f"({stats.corrupt_plans} != {n_cache})")
    qdir = pathlib.Path(cache_dir) / "quarantine"
    n_qfiles = len(list(qdir.iterdir())) if qdir.is_dir() else 0
    gate(faulted.quarantined == n_qfiles and faulted.quarantined >= n_cache,
         f"quarantine counter matches the quarantine dir "
         f"({faulted.quarantined} != {n_qfiles} files, >= {n_cache})")
    gate(faulted.tokens_per_s >= clean.tokens_per_s / 1.5,
         f"chaos tokens/sec within the 1.5x serve gate "
         f"({faulted.tokens_per_s:.1f} vs clean {clean.tokens_per_s:.1f})")
    gate(clean.degradations == 0 and clean.quarantined == 0
         and clean.n_poisoned == 0,
         f"clean pass has zero resilience counters (degradations="
         f"{clean.degradations} quarantined={clean.quarantined} "
         f"poisoned={clean.n_poisoned})")

    row = _row(f"{preset}_chaos", cfg, faulted)
    return {"row": row, "report": faulted, "clean": clean,
            "failures": failures, "plan": plan.to_json()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=sorted(PRESET_ARGS))
    ap.add_argument("--faults", default=None, choices=("chaos",),
                    help="run the seeded fault harness instead of the "
                         "clean bench (gates internally, exit 1 on any "
                         "gate failure)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the gate-format rows file")
    ap.add_argument("--report", default=None,
                    help="write the full ServeReport JSON")
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache
    compile_cache.configure()

    if args.faults == "chaos":
        out = chaos(args.preset)
        row, report = out["row"], out["report"]
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        for f in out["failures"]:
            print(f"{args.faults.upper()} GATE FAILED: {f}")
        if not out["failures"]:
            print(f"{args.faults} gates passed: "
                  f"{len(out['plan']['faults'])} planned faults and one "
                  "NaN decode row injected, every counter matched")
        if args.report:
            with open(args.report, "w") as fh:
                json.dump({args.faults: report.to_json(),
                           "clean": out["clean"].to_json(),
                           "plan": out["plan"],
                           "failures": out["failures"]}, fh, indent=1)
        return 1 if out["failures"] else 0

    out = bench(args.preset)
    row, report = out["row"], out["report"]
    print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"preset": args.preset, "rows": [row]}, f, indent=2)
            f.write("\n")
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report.to_json(), f, indent=1)
    return 1 if (report.decode_recompiles or report.pallas_fallbacks
                 or report.degradations or report.quarantined) else 0


if __name__ == "__main__":
    sys.exit(main())
