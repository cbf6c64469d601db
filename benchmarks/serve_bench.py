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
jax rung), one corrupted on-disk plan, and one NaN decode step — and
gates internally:

* every non-poisoned request completes, tokens byte-identical to the
  clean run;
* ``degradations`` equals the number of compile faults in the plan,
  ``quarantined``/``corrupt_plans`` match the cache faults exactly, and
  ``n_poisoned`` matches the NaN faults;
* chaos tokens/sec stays within the same 1.5x collapse gate, measured
  against this runner's own clean pass.

Heal mode (``--faults heal``, the CI ``chaos`` job's second step) drives
the self-healing loop end-to-end: a transient decode fault fires once
and stops, the watchdog demotes decode to the jax rung, and the health
ledger's half-open probe must re-promote back to the grouped pallas
rung mid-run — with the first probe itself faulted, so the breaker
re-opens at doubled cool-down before the second probe heals.  Gates pin
``repromotions`` / ``probes`` / ``probe_failures`` EXACTLY against the
plan and require tokens byte-identical to the clean pass.
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
# ungrouped rung (first compile of warmup -> ladder lands on jax), the
# first on-disk plan read corrupted, one NaN decode step mid-run
def _chaos_plan():
    from repro import resilience as RZ
    return RZ.FaultPlan([
        RZ.FaultSpec(site="compile:grouped", indices=(0,), kind="raise",
                     message="chaos: grouped lowering down"),
        RZ.FaultSpec(site="compile:ungrouped", indices=(0,), kind="raise",
                     message="chaos: ungrouped lowering down"),
        RZ.FaultSpec(site="cache:get_plan", indices=(0,), kind="corrupt"),
        RZ.FaultSpec(site="serve:logits", indices=(2,), kind="nan"),
    ], seed=0)


# the seeded heal plan: one transient decode fault (fires once, then
# the rung is healthy again) plus one faulted re-promotion probe, so
# the breaker re-opens at doubled cool-down before the second probe
# swaps the pallas rung back in
def _heal_plan():
    from repro import resilience as RZ
    return RZ.FaultPlan([
        RZ.FaultSpec(site="serve:decode", indices=(2,), kind="raise",
                     message="heal: transient decode fault"),
        RZ.FaultSpec(site="serve:probe", indices=(0,), kind="raise",
                     message="heal: probe still cold"),
    ], seed=0)


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
        f"repromotions={report.repromotions}",
        f"probes={report.probes}",
        f"probe_failures={report.probe_failures}",
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
    from repro.launch.serve import run

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
        faulted = run(cfg)
    stats = pipeline.default_cache().stats

    failures = []

    def gate(ok: bool, what: str):
        if not ok:
            failures.append(what)

    poisoned = {f["rid"] for f in faulted.failures
                if f["reason"] in ("nonfinite_logits",
                                   "nonfinite_prefill")}
    n_nan = plan.expected_count("serve:logits")
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


def heal(preset: str) -> dict:
    """The self-healing harness: clean pass, then the same preset under
    a transient decode fault plus a faulted first probe, with a short
    re-promotion window.  Gates pin the full breaker lifecycle —
    demote -> failed probe (doubled cool-down) -> successful probe ->
    re-promotion to the grouped pallas rung — EXACTLY against the plan."""
    import dataclasses

    from repro import pipeline, resilience as RZ
    from repro.launch.serve import run

    # a short probe window so the lifecycle completes inside the preset
    # trace: demote ~tick 2, failed probe 3 ticks later, breaker doubles
    # to 6, healing probe ~tick 12
    cfg = dataclasses.replace(_presets()[preset], repromote_after=3)
    cache_dir = tempfile.mkdtemp(prefix="repro-heal-cache-")
    os.environ["REPRO_KERNEL_CACHE"] = cache_dir
    pipeline.reset_default_cache()

    clean = run(cfg)
    pipeline.reset_default_cache()
    plan = _heal_plan()
    with RZ.faults(plan):
        faulted = run(cfg)

    failures = []

    def gate(ok: bool, what: str):
        if not ok:
            failures.append(what)

    n_decode = plan.expected_count("serve:decode")
    n_probe_faults = plan.expected_count("serve:probe")

    gate(plan.fired_count() == len(plan.specs),
         f"every planned fault fires (fired {plan.fired_count()}/"
         f"{len(plan.specs)}: {plan.fired})")
    gate(faulted.degradations == n_decode,
         f"watchdog demotions match the plan "
         f"({faulted.degradations} != {n_decode})")
    gate(faulted.repromotions == n_decode,
         f"every demotion healed: re-promotions match the plan "
         f"({faulted.repromotions} != {n_decode})")
    gate(faulted.probe_failures == n_probe_faults,
         f"probe failures match the plan "
         f"({faulted.probe_failures} != {n_probe_faults})")
    gate(faulted.probes == n_decode + n_probe_faults,
         f"probe count matches the plan: one per planned probe fault "
         f"plus one healing probe ({faulted.probes} != "
         f"{n_decode + n_probe_faults})")
    gate(faulted.decode_backend == "pipeline-pallas",
         f"decode ended the run back on the grouped pallas rung "
         f"(ended on {faulted.decode_backend!r})")
    gate(faulted.n_completed == clean.n_completed,
         f"a transient fault poisons nothing: all requests complete "
         f"({faulted.n_completed} != {clean.n_completed})")
    mismatched = [r for r in clean.tokens
                  if clean.tokens[r] != faulted.tokens.get(r)]
    gate(not mismatched,
         f"tokens byte-identical to the clean run across demote AND "
         f"re-promote (mismatched rids {mismatched})")
    gate(faulted.decode_recompiles == 0,
         f"demotion/probe compiles stay off the strict-no-recompile "
         f"books ({faulted.decode_recompiles} != 0)")
    gate(faulted.quarantined == 0 and faulted.n_poisoned == 0,
         f"no cache or numeric casualties (quarantined="
         f"{faulted.quarantined} poisoned={faulted.n_poisoned})")
    # no 1.5x throughput gate here: the heal pass pays two mid-run jit
    # rebuilds (the demotion build and the probe re-compile) inside a
    # deliberately tiny CI trace, so wall time is compile-dominated by
    # design.  A 20x collapse guard still catches hangs and pathological
    # probe loops
    gate(faulted.tokens_per_s >= clean.tokens_per_s / 20.0,
         f"heal tokens/sec within the 20x hang guard "
         f"({faulted.tokens_per_s:.1f} vs clean {clean.tokens_per_s:.1f})")
    gate(clean.repromotions == 0 and clean.probes == 0
         and clean.probe_failures == 0 and clean.degradations == 0,
         f"clean pass has zero self-healing counters (repromotions="
         f"{clean.repromotions} probes={clean.probes} probe_failures="
         f"{clean.probe_failures} degradations={clean.degradations})")

    row = _row(f"{preset}_heal", cfg, faulted)
    return {"row": row, "report": faulted, "clean": clean,
            "failures": failures, "plan": plan.to_json()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=sorted(PRESET_ARGS))
    ap.add_argument("--faults", default=None, choices=("chaos", "heal"),
                    help="run a seeded fault harness instead of the "
                         "clean bench (gates internally, exit 1 on any "
                         "gate failure)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the gate-format rows file")
    ap.add_argument("--report", default=None,
                    help="write the full ServeReport JSON")
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache
    compile_cache.configure()

    if args.faults in ("chaos", "heal"):
        out = (chaos if args.faults == "chaos" else heal)(args.preset)
        row, report = out["row"], out["report"]
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        for f in out["failures"]:
            print(f"{args.faults.upper()} GATE FAILED: {f}")
        if not out["failures"]:
            print(f"{args.faults} gates passed: "
                  f"{len(out['plan']['faults'])} faults injected, every "
                  "counter matched the plan")
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
